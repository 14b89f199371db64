"""Seconds inside the window spent tracing, lowering and compiling
programs (a compile that the persistent cache serves counts its lookup),
from ``jax.monitoring``'s duration spans, nested spans merged."""
from bench.tracedata import union


def read(run):
    lo, hi = run.window_wall
    spans = [(max(s, lo), min(e, hi)) for s, e in run.compile_spans
             if min(e, hi) > max(s, lo)]
    return float(sum(e - s for s, e in union(spans)))
