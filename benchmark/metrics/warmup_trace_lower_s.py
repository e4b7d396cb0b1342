"""Seconds the warm-up fit spent in JAX's trace of its programs into jaxprs
and their lowering to StableHLO: the warm-up `train` span's `trace_s` +
`lower_s`, the part of making a program that `compile_s` (the backend's
build and cache loads) does not read."""
from lib import build_spans, spans


def read(run):
    return build_spans.trace_lower_s(spans.warmup_tree(run))
