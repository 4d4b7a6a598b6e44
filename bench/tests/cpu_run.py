"""``bench/run.py`` on the CPU devices of this process, over a copy of the
benchmark:

    python -m bench.tests.cpu_run <root> <bench_dir> <run.py arguments>

Like the tests' ``tiny`` fixture, it skips the look for a TPU (a cell
takes the first of the process's devices) and the persistent compile
cache."""
import sys

import jax

from bench import run, spec


def devices(chips: int):
    devs = jax.devices()
    if len(devs) < chips:
        raise run.NoChip(f"the cell needs {chips} devices, found {len(devs)}")
    return devs[:chips]


def main(argv) -> int:
    spec.ROOT, spec.BENCH_DIR = argv[0], argv[1]
    run.require_chip = devices
    run.enable_compile_cache = lambda root: "off"
    return run.main(argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
