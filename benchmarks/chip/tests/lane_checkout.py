"""A copied checkout whose ``BENCHMARK.json`` names only the tenant-lane
fixture cell (``fixtures/lanes4_q1*.json``: four tenants of Q1), with the
program beside it, and the fixture's runs in it: ``readings.py`` runs the
sound run and each planted fault in one process, on one CPU device or on
four virtual ones."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
FIXTURES = os.path.join(HERE, "tests", "fixtures")
CONFIG, CELL = "lanes4_q1", "lanes4_q1.saturate"


def make(dst, chips: int) -> None:
    """The checkout: this harness, the fixture as its only cell on
    ``chips`` chips, and the program (a link to this checkout's)."""
    bench_dir = os.path.join(dst, "benchmarks", "chip")
    shutil.copytree(HERE, bench_dir, ignore=shutil.ignore_patterns(
        ".jax_cache", "out", "__pycache__"))
    shutil.copy(os.path.join(FIXTURES, CONFIG + ".json"),
                os.path.join(bench_dir, "configs"))
    shutil.copy(os.path.join(FIXTURES, CELL + ".json"),
                os.path.join(bench_dir, "workloads"))
    os.symlink(os.path.join(CHECKOUT, "src"), os.path.join(dst, "src"))
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{
        "name": CONFIG, "source": "https://arxiv.org/abs/2002.04436",
        "file": f"benchmarks/chip/configs/{CONFIG}.json", "reduced": [],
        "why": "test fixture: four tenant lanes of Q1"}]
    bench["workloads"] = [{
        "name": CELL, "config": CONFIG, "traffic": "saturate",
        "chips": chips, "why": "test fixture"}]
    bench["end_to_end"] = [
        {k: v for k, v in m.items() if k != "workloads"}
        for m in bench["end_to_end"]
        if m["name"] in ("events_per_s", "setup_s")]
    bench["per_layer"] = []
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)


def readings(dst, chips: int, faults: list[str], seed: int = 1) -> dict:
    """Per fault (``none``: a sound run), what ``readings.py`` printed
    for the fixture cell at toy size on ``chips`` CPU devices."""
    make(dst, chips)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/readings.py", "--workload", CELL,
         "--seconds", "60", "--seeds", str(seed), "--rehearse",
         "--faults", ",".join(faults)],
        cwd=dst, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = {}
    for ln in proc.stdout.splitlines():
        if ln.startswith("{"):
            d = json.loads(ln)
            out[d["fault"]] = d
    return out
