"""Faults planted under the timed path, and the control.

Each is a mixin put in front of the runtime's own class once set-up is
done, so the window runs the broken path and the reference judges its
output.  The benchmark's runs never plant one; ``run.py --fault`` does, for
the readings that set the limits and for the tests that see ``correct``
come out false.

  stuck      a step that returns its state unchanged
  half       the second half of every chunk left out (its events made
             inert: no class, no window opened)
  altered    an answer altered where it is produced: one decoded match per
             chunk moved to the next event, or, where only counts are
             output, the first query's completions counted twice (with
             tenant lanes, in the last lane only, which sits on the last
             chip of a mesh: one broken tenant among sound ones)
  duplicate  the control: exactly-once broken, the first admitted region
             of the window processed twice, as an at-least-once
             redelivery between admission and the engine would (a
             duplicated push is no control: where the token bucket is
             drained, admission refuses it)
  lane_swap  the control for tenant isolation (lane runtimes only): the
             first and the last lane exchange their carries after the
             window's first dispatch

and, for the readings of the numbers whose limits are not exact:

  shed_all        Algorithm 2 drops every live PM at each fire: the
                  latency model's slope is made a million times steeper
                  (set again after every refresh), so each fire finds no
                  PM count under the bound
  shed_none       Algorithm 1 never fires: the latency model predicts no
                  processing time (set again after every refresh)
  refresh_skipped the model refresh never runs
  stale_tables    the refresh runs but keeps the utility tables it had
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class _Stuck:
    def _run(self, chunk, start):
        keep = jax.tree.map(jnp.copy, self.carry)
        _, outs = super()._run(chunk, start)
        return keep, outs

    def _run_grouped(self, piece, start, g):
        keep = jax.tree.map(jnp.copy, self.carry)
        _, vecs = super()._run_grouped(piece, start, g)
        return keep, vecs


class _Half:
    def _run_region(self, start, region, n_chunks):
        if region is not None:
            cs = self.rt.chunk_size
            keep = jnp.asarray((np.arange(n_chunks * cs) % cs) < cs // 2)
            region = region._replace(
                ev_class=jnp.where(keep[:, None], region.ev_class, 0),
                ev_open=jnp.where(keep[:, None], region.ev_open, False))
        return super()._run_region(start, region, n_chunks)


def _double_first(rt, old_cc, carry):
    """The first query's completions since ``old_cc`` counted twice: in
    the last lane where ``rt`` has lanes."""
    at = (rt.num_lanes - 1, 0) if hasattr(rt, "num_lanes") else 0
    cc = carry.complex_count
    return carry._replace(complex_count=cc.at[at].add(cc[at] - old_cc[at]))


class _Altered:
    def _run(self, chunk, start):
        if not hasattr(self, "matches"):
            old = jnp.copy(self.carry.complex_count)
            carry, outs = super()._run(chunk, start)
            return _double_first(self, old, carry), outs
        before = [set(m) for m in self.matches]
        carry, outs = super()._run(chunk, start)
        for p, m in enumerate(self.matches):
            new = sorted(m - before[p])
            if new:
                o, b, e = new[0]
                m.discard(new[0])
                m.add((o, b, e + 1))
                break
        return carry, outs

    def _run_grouped(self, piece, start, g):
        old = jnp.copy(self.carry.complex_count)
        carry, vecs = super()._run_grouped(piece, start, g)
        return _double_first(self, old, carry), vecs


class _Duplicate:
    def _run_region(self, start, region, n_chunks):
        if region is None or getattr(self, "_dup_done", False):
            return super()._run_region(start, region, n_chunks)
        self._dup_done = True
        again = jax.tree.map(jnp.copy, region)
        out = super()._run_region(start, region, n_chunks)
        return out + super()._run_region(start, again, n_chunks)


class _LaneSwap:
    def _after_chunk(self, out):
        super()._after_chunk(out)
        if not getattr(self, "_swapped", False):
            self._swapped = True
            ab, ba = jnp.array([0, self.num_lanes - 1]), \
                jnp.array([self.num_lanes - 1, 0])
            self.carry = jax.tree.map(lambda x: x.at[ab].set(x[ba]),
                                      self.carry)


def _scale_f(rt, a_by: float, b_to: float | None):
    f = rt.model.f_model
    rt.model = rt.model._replace(f_model=type(f)(
        f.a * a_by, f.b if b_to is None else jnp.full_like(f.b, b_to),
        f.kind))


class _ShedAll:
    def _planted(self):
        _scale_f(self, 1e6, None)

    def _maybe_refresh(self):
        did = super()._maybe_refresh()
        self._planted()
        return did


class _ShedNone:
    def _planted(self):
        _scale_f(self, 0.0, -1e9)

    def _maybe_refresh(self):
        did = super()._maybe_refresh()
        self._planted()
        return did


class _RefreshSkipped:
    def _maybe_refresh(self):
        return False


class _StaleTables:
    def _maybe_refresh(self):
        old = self.model.ut_tables
        did = super()._maybe_refresh()
        self.model = self.model._replace(ut_tables=old)
        return did


FAULTS = {"stuck": _Stuck, "half": _Half, "altered": _Altered,
          "duplicate": _Duplicate, "lane_swap": _LaneSwap,
          "shed_all": _ShedAll,
          "shed_none": _ShedNone, "refresh_skipped": _RefreshSkipped,
          "stale_tables": _StaleTables}


def plant(runtime, name: str) -> None:
    """Put fault ``name`` in front of ``runtime``'s class, in place."""
    if name == "lane_swap" and getattr(runtime, "num_lanes", 1) < 2:
        raise ValueError("lane_swap needs a runtime of two lanes or more")
    mixin = FAULTS[name]
    runtime.__class__ = type(f"{mixin.__name__[1:]}{type(runtime).__name__}",
                             (mixin, type(runtime)), {})
    if hasattr(runtime, "_planted"):
        runtime._planted()
