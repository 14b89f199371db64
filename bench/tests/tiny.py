"""The benchmark's cells cut to a size a CPU test run holds."""
import time

from bench import harness

TINY = {"g500-s22.pagerank": {"scale": 9}}
CPU_PEAKS = {"hbm_bytes_per_s": 819e9}


def cell(name, root=harness.ROOT):
    spec = harness.load_spec(root)
    c = harness.resolve_cell(spec, name, root)
    c.config.update(TINY.get(name, {}))
    return spec, c


def run(name, *, seconds=0.3, trace=False, seed=1234567891234,
        root=harness.ROOT):
    """One run of the cell on the CPU, past the harness's look for a
    chip; returns the result line's object."""
    import jax
    spec, c = cell(name, root)
    return harness.execute(spec, c, seed, seconds, trace, jax.devices()[:1],
                           CPU_PEAKS, time.perf_counter(), root)
