"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the checkout's
root; it names a configuration (``bench/configs/<name>.json`` and its
plain reference ``<name>_ref.py``) and a traffic mix
(``bench/traffic/<name>.json``), whose ``kind`` selects the runner
``bench/runners/<kind>.py``.  The runner sets up, warms up every shape,
measures for ``--seconds``, then checks what the timed path produced
against the reference.  With ``--trace 1`` the window runs under the JAX
profiler and the per-layer metrics are read from the trace and the
program's counters by the readers ``bench/metrics/<name>.py``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` and,
last, ``checks``); the checks are also the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402


class NoDevice(Exception):
    """The machine lacks the accelerator the cell needs."""


def require_tpu(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX sees platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} TPU chips, JAX sees "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def configure_jax(root: Path) -> None:
    """Persistent compilation cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), holding every program however
    quickly it compiled, so that a second run compiles nothing."""
    import jax
    from repro.launch.cache import configure_compile_cache
    configure_compile_cache(root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = harness.load_benchmark(ROOT)
        cell = harness.load_cell(ROOT, bench, args.workload)
        device = require_tpu(cell.chips)
        configure_jax(ROOT)
        result = harness.run_cell(ROOT, bench, cell, seed=args.seed,
                                  seconds=args.seconds,
                                  trace=bool(args.trace), device=device,
                                  t_start=T_START)
    except NoDevice as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    except harness.BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(harness.trace_dir(ROOT, args.workload),
                      ignore_errors=True)
    for line in harness.check_lines(result):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(harness.result_line(result)))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
