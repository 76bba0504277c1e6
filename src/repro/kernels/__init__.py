"""Pallas TPU kernels for the system's compute hot spots.

  flash_attention.py — VMEM-resident online-softmax attention (the
      memory-term bottleneck of every ≥32k attention cell; §Perf).
  block_step.py      — the production CEP kernel: W events per launch
      with the PM store resident (DESIGN.md §10).
  nfa_transition.py  — the CEP operator's hot loop (paper §III): per-event
      PM advance as vector selects instead of a gather.
  shed_select.py     — Algorithm 2 without the sort: fused O(1) utility
      lookup + histogram-threshold selection.
  ops.py             — jit'd public wrappers.
  ref.py             — pure-jnp oracles (the tests' allclose targets).

Every CEP kernel compiles for a TPU v5e with Mosaic
(tests/test_tpu_compile.py, no chip needed) and runs on the chip through
``chip_smoke.py``.  Off-TPU the same kernels run interpreted
(``ops.default_interpret``) and are checked against the oracles across
shape sweeps (tests/test_kernels.py, tests/test_block_backend.py).
"""
