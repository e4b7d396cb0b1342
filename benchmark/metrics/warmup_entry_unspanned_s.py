"""Seconds between the warm-up fit's train() call and its first tree_block
that lie under none of its leaf spans (its host steps, and JAX's trace,
lowering and build of each program as `jit_*` leaves): what `bin_upload_s`
holds that no span names."""
from lib import build_spans, spans


def read(run):
    return build_spans.entry_unspanned_s(spans.warmup_tree(run))
