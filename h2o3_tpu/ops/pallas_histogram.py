"""Pallas TPU kernels for the gradient-histogram hot op (``tpu_hist``).

Reference semantics: ``hex/tree/DHistogram.java:433`` (updateHisto — per
(node, feature, bin) accumulation of {Σg, Σh, Σw}) as driven by
``hex/tree/ScoreBuildHistogram2.java:273-280`` (private per-thread
histograms, then merge) and the native ``grow_gpu_hist`` updater in the
XGBoost extension (SURVEY.md §2.3).

Two TPU-native designs, both turning the scatter-add into dense MXU work:

**Fixed-layout node-matmul kernel** (default for K·C ≤ 512, i.e. every
level of a depth ≤ 6 tree): rows NEVER move. Grid over (feature-block,
row-tile); each step computes ``one_hot(bins)[R, Fb·B1]ᵀ ⊗
node_masked_vals[R, K·C]`` as ONE dot_general on the MXU and accumulates
into a VMEM-resident [Fb·B1, K·C] block revisited across row tiles. There
is no sort, no scatter, no partition maintenance, no row is moved. The
histogram for ALL nodes of the level materializes in one pass.

**Sorted tile-per-node kernel** (fallback for deep levels, K·C > 512,
where the all-nodes output exceeds VMEM): a 1-D grid with
``pltpu.PrefetchScalarGridSpec`` where the output BlockSpec's index map
reads the prefetched node id — each grid step's output block IS that
node's (F, C, B) slab, accumulated in VMEM across that node's tiles. Its
operands are every node's rows in stable order, each node padded to a
row-tile multiple, and building them (``_prep_gathered``) is what a sorted
level costs. On a v5e at 6M x 28 rows the key-value sort takes 11-14 ms
and the kernel 44 ms, but MOVING the rows is priced by the row and by each
group of 8 int32 columns of it, not by its bytes: a gather of 6.26M rows
takes 44-73 ms for up to 8 columns and 146-215 ms for 28-32, and a scatter
of the same rows 3.3 times the gather. So the preparation moves every row
once, by one gather of a narrow row that carries the codes (several to a
word) and g, h, w together, and holds no scatter, no ``bincount``, no
zero-filled buffer and no per-row table lookup: the index work is per tile
(PERF.md section 6, PR 29).

The portable XLA scatter path in ``h2o3_tpu/ops/histogram.py`` is the
correctness oracle; ``tests/test_pallas_histogram.py`` checks parity in
interpreter mode on CPU.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from h2o3_tpu.ops.bitpack import pack_words, unpack_words

# channels: 0=Σg, 1=Σh, 2=Σw(count); a 4th pad channel keeps the matmul
# operand lane-friendly.
_C = 4


def _out_sds(shape, dtype, vma):
    """Kernel output shape carrying the shard-varying axes ``vma`` (empty
    outside ``shard_map``)."""
    return jax.ShapeDtypeStruct(
        shape, dtype, vma=frozenset(vma) if vma else None)

#: node-matmul kernel applies while K*_C <= this (VMEM budget for the
#: [Fb*B1, K*C] accumulator + operands; ~16 MB/core on v5e)
_NODE_MATMUL_MAX_KC = 512

#: feature-block width of the node-matmul kernel grid (callers preparing an
#: aligned feature-major bins copy must pad features to a multiple of this)
_FEAT_BLOCK = 8

#: row-tile height; callers pre-padding rows must use a multiple of this
#: (bigger tiles amortize per-step VPU overhead; 2048 overflows VMEM)
_ROW_TILE = 512


# ---------------------------------------------------------------------------
# fixed-layout node-matmul kernel


def _nm_kernel(
    jmod_ref, bins_ref, node_ref, vals_ref, out_ref, oh_ref, *,
    n_feat_b, n_bins1, n_nodes
):
    """One grid step = one (feature-block, row-tile).

    jmod_ref: [B1, 1] f32 CONSTANT (the bin-index iota), loaded once —
    replaces a per-step 3-D int32 iota materialization (the VPU pass that
    used to dominate the whole kernel); bins_ref: [Fb, R] int32
    (feature-major — Mosaic wants the long axis in lanes); node_ref:
    [R, 1] int32 (-1 inactive; 2-D so the block layout matches XLA's 1-D
    tiling); vals_ref: [R, C] f32; out_ref: [1, K*C, Fb*B1] f32 (revisited
    across the row-tile grid dimension — accumulates in VMEM).

    Orientation: the MXU lane (N) dimension is Fb*B1 (~2000, always full);
    K*C sits in the sublane (M) dimension whose padding granularity is 8.
    The transposed orientation ([Fb*B1, K*C]) padded K*C up to 128 lanes,
    wasting up to 97% of the MXU at shallow levels (K*C = 4 at the root).
    """
    r = node_ref.shape[0]
    rt = pl.program_id(1)
    dtype = vals_ref.dtype

    # [Fb*B1, R] one-hot of bin codes, written per-feature into a VMEM
    # scratch: each 2-D compare pairs a lane-splat ([B1, 1] iota constant)
    # with a sublane-splat ([1, R] bin row) — both native broadcasts, so
    # the whole construction is ~one write pass (no 3-D broadcast
    # materialization, no concat). Bin codes <= 256 are exact in f32.
    binsb = bins_ref[...].astype(jnp.float32)  # [Fb, R] (tiny)
    jm = jmod_ref[...]  # [B1, 1] f32 iota constant
    for f in range(n_feat_b):
        # compare in f32 (codes <= 256 exact); the 0/1 mask is stored at
        # the histogram dtype — in bf16 mode this halves the dominant
        # VMEM write traffic of the whole kernel, losslessly (0/1 exact)
        oh_ref[f * n_bins1 : (f + 1) * n_bins1, :] = (
            jm == binsb[f][None, :]
        ).astype(dtype)
    onehot = oh_ref[...]

    # [R, K*C] node-masked values in ~ONE VPU pass: lane j carries node
    # j//C, channel j%C. A lane CONCAT of K copies of vals (Mosaic
    # handles lane concat; it cannot merge a (K, C) reshape) replaces
    # the former per-channel where+add loop (3 select passes -> 1).
    # Channel 3 is already the zero pad, so no extra masking per channel.
    node = node_ref[...]  # [R, 1]
    vals = vals_ref[...]  # [R, C]
    kc = n_nodes * _C
    iota_kc = jax.lax.broadcasted_iota(jnp.int32, (r, kc), 1)
    m_node = (iota_kc // _C) == node  # node<0 never matches
    tiled = jnp.concatenate([vals] * n_nodes, axis=1)  # [R, K*C]
    vals_k = jnp.where(m_node, tiled, jnp.zeros((), dtype))

    # [K*C, Fb*B1] = vals_kᵀ ⊗ onehotᵀ — contraction over rows on the MXU
    # (bf16 operands run at 2x the f32 MXU rate; accumulation stays f32)
    slab = jax.lax.dot_general(
        vals_k, onehot, (((0,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[None]

    @pl.when(rt == 0)
    def _():
        out_ref[...] = slab

    @pl.when(rt != 0)
    def _():
        out_ref[...] = out_ref[...] + slab


def _nm_classes_kernel(
    jmod_ref, bins_ref, node_ref, vals_ref, out_ref, oh_ref, vk_ref, *,
    n_feat_b, n_bins1, n_nodes, n_classes
):
    """``_nm_kernel`` for the K class trees of a boosting round: one grid
    step = one (feature-block, row-tile), the tile's one-hot of codes built
    ONCE and contracted against the node-masked values of every class tree.

    node_ref: [K, R] int32, a row's node in each class tree (-1 inactive);
    vals_ref: [3K, R], row ch * K + k the row's channel ch (Σg, Σh, Σw) of
    class k; both lane-dense (rows in lanes). vk_ref: [3K*n, R] scratch,
    row (3k + ch) * n + j = channel ch of class k where the row's node in
    tree k is j, else 0; out_ref: [1, 3K*n, Fb*B1] f32 (revisited across
    the row-tile grid dimension — accumulates in VMEM). The MXU's M
    dimension is the K*n*3 (class, node, channel) slots: K times a tree's
    at every level, in one launch."""
    rt = pl.program_id(1)
    dtype = oh_ref.dtype
    binsb = bins_ref[...].astype(jnp.float32)  # [Fb, R]
    jm = jmod_ref[...]  # [B1, 1] f32 iota constant
    for f in range(n_feat_b):
        oh_ref[f * n_bins1 : (f + 1) * n_bins1, :] = (
            jm == binsb[f][None, :]
        ).astype(dtype)

    node = node_ref[...]  # [K, R]
    # the select in float32, whose layout is the mask's; the stored operand
    # is then cast (a bfloat16 select would relayout the mask)
    vals = vals_ref[...].astype(jnp.float32)  # [3K, R]
    at = jax.lax.broadcasted_iota(jnp.int32, (n_nodes, node.shape[1]), 0)
    for k in range(n_classes):
        mine = at == node[k][None, :]  # [n, R]; node < 0 never matches
        for ch in range(3):
            row = (3 * k + ch) * n_nodes
            vk_ref[row : row + n_nodes, :] = jnp.where(
                mine, vals[ch * n_classes + k][None, :], 0.0).astype(dtype)

    # [3K*n, Fb*B1] = vk ⊗ onehotᵀ — contraction over the tile's rows
    slab = jax.lax.dot_general(
        vk_ref[...], oh_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[None]

    @pl.when(rt == 0)
    def _():
        out_ref[...] = slab

    @pl.when(rt != 0)
    def _():
        out_ref[...] = out_ref[...] + slab


def _build_classes_nodematmul(
    bins, nodes, g, h, n_nodes: int, n_bins1: int,
    row_tile: int, feat_block: int, interpret: bool, vma: tuple,
    bins_fm=None, rw=None, dtype=jnp.float32,
):
    """The node-matmul histogram of K class trees in one launch: nodes, g, h
    [K, N] class-major (the rows in lanes, no 128-lane padding). Returns
    [K * n_nodes, F, n_bins1, 3] float32, class-major."""
    n_classes, n = nodes.shape
    n_feat = bins.shape[1]
    r = row_tile
    fb = min(feat_block, n_feat)
    padf = (-n_feat) % fb
    n_feat_p = n_feat + padf
    if not (bins_fm is not None and bins_fm.shape == (n_feat_p, n) and n % r == 0):
        if n % r:
            pad = (-n) % r
            bins = jnp.pad(bins, ((0, pad), (0, 0)))
            nodes = jnp.pad(nodes, ((0, 0), (0, pad)), constant_values=-1)
            g = jnp.pad(g, ((0, 0), (0, pad)))
            h = jnp.pad(h, ((0, 0), (0, pad)))
            if rw is not None:
                rw = jnp.pad(rw, (0, pad))
            n = n + pad
        if padf:
            bins = jnp.pad(bins, ((0, 0), (0, padf)))
        bins_fm = bins.T

    w = (nodes >= 0).astype(jnp.float32)
    cw = w if rw is None else w * rw.astype(jnp.float32)[None, :]
    # row ch * K + k: channel ch of class k, bf16 mode rounds the inputs.
    # A concatenation: stacking the channels inside each class instead
    # ([K, 3, N] reshaped to [3K, N]) took the TPU compiler 85 s at 4.9M rows
    vals = jnp.concatenate(
        [g.astype(jnp.float32) * w, h.astype(jnp.float32) * w, cw], axis=0
    ).astype(dtype)

    n_ftiles = n_feat_p // fb
    slots = 3 * n_classes * n_nodes
    jmod = jnp.asarray(np.arange(n_bins1)[:, None], dtype=jnp.float32)
    if vma:
        jmod = jax.lax.pcast(jmod, tuple(vma), to="varying")

    out = pl.pallas_call(
        partial(_nm_classes_kernel, n_feat_b=fb, n_bins1=n_bins1,
                n_nodes=n_nodes, n_classes=n_classes),
        grid=(n_ftiles, n // r),
        in_specs=[
            pl.BlockSpec((n_bins1, 1), lambda f, t: (0, 0)),
            pl.BlockSpec((fb, r), lambda f, t: (f, t)),
            pl.BlockSpec((n_classes, r), lambda f, t: (0, t)),
            pl.BlockSpec((3 * n_classes, r), lambda f, t: (0, t)),
        ],
        scratch_shapes=[pltpu.VMEM((fb * n_bins1, r), dtype),
                        pltpu.VMEM((slots, r), dtype)],
        out_specs=pl.BlockSpec((1, slots, fb * n_bins1), lambda f, t: (f, 0, 0)),
        out_shape=_out_sds((n_ftiles, slots, fb * n_bins1), jnp.float32, vma),
        interpret=interpret,
    )(jmod, bins_fm, nodes, vals)

    # [Ft, (K, 3, n), Fb*B1] -> [K*n, F, B1, 3]
    out = out.reshape(n_ftiles, n_classes, 3, n_nodes, fb, n_bins1)
    out = jnp.transpose(out, (1, 3, 0, 4, 5, 2)).reshape(
        n_classes * n_nodes, n_feat_p, n_bins1, 3)
    return out[:, :n_feat]


def _build_histogram_nodematmul(
    bins, nodes, g, h, n_nodes: int, n_bins1: int,
    row_tile: int, feat_block: int, interpret: bool, vma: tuple,
    bins_fm=None, rw=None, dtype=jnp.float32,
):
    n, n_feat = bins.shape
    r = row_tile
    fb = min(feat_block, n_feat)
    padf = (-n_feat) % fb
    n_feat_p = n_feat + padf
    if bins_fm is not None and bins_fm.shape == (n_feat_p, n) and n % r == 0:
        pass  # caller prepared the aligned feature-major copy: zero prep here
    else:
        if n % r:
            pad = (-n) % r
            bins = jnp.pad(bins, ((0, pad), (0, 0)))
            nodes = jnp.pad(nodes, (0, pad), constant_values=-1)
            g = jnp.pad(g, (0, pad))
            h = jnp.pad(h, (0, pad))
            if rw is not None:
                rw = jnp.pad(rw, (0, pad))
            n = n + pad
        if padf:
            # pad features with bin code 0: sliced away after the reshape below
            bins = jnp.pad(bins, ((0, 0), (0, padf)))
        bins_fm = bins.T  # [Fp, N] feature-major: rows land in the lane axis

    # The kernel takes the node ids as [N, 1] and g, h, w as [N, C]: row-major
    # operands whose rows fill 1 and 4 of 128 lanes. Without the barrier XLA
    # may hoist those reshapes into the producers of the [N] vectors and
    # compute them (node ids, the sample's random draws, gradients, routing)
    # in that 128-lane padded layout, cloned into every consumer. Whether it
    # does flips with any change to the block: with rungs of 16 and 32 in the
    # node ladder it took the depth-10 block from 978 to 1,544 ms a tree and
    # its temporaries from 5.1 to 15.6 GB (PERF.md section 6, PR 35). The
    # barrier pins the vectors as the lane-dense [N] arrays they are; the
    # reshape to the kernel's layout is then one small pass of its own.
    nodes, g, h, rw = jax.lax.optimization_barrier((nodes, g, h, rw))
    w = (nodes >= 0).astype(jnp.float32)
    cw = w if rw is None else w * rw.astype(jnp.float32)
    vals = jnp.stack(
        [g.astype(jnp.float32) * w, h.astype(jnp.float32) * w, cw, jnp.zeros_like(w)],
        axis=1,
    ).astype(dtype)  # [N, C]; bf16 mode rounds inputs, accumulates f32

    n_ftiles = n_feat_p // fb
    n_rtiles = n // r

    # resident constant: one-hot sublane b (within a feature) covers bin b
    jmod = jnp.asarray(np.arange(n_bins1)[:, None], dtype=jnp.float32)
    if vma:
        jmod = jax.lax.pcast(jmod, tuple(vma), to="varying")

    out = pl.pallas_call(
        partial(_nm_kernel, n_feat_b=fb, n_bins1=n_bins1, n_nodes=n_nodes),
        grid=(n_ftiles, n_rtiles),
        in_specs=[
            pl.BlockSpec((n_bins1, 1), lambda f, t: (0, 0)),
            pl.BlockSpec((fb, r), lambda f, t: (f, t)),
            pl.BlockSpec((r, 1), lambda f, t: (t, 0)),
            pl.BlockSpec((r, _C), lambda f, t: (t, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((fb * n_bins1, r), dtype)],
        out_specs=pl.BlockSpec(
            (1, n_nodes * _C, fb * n_bins1), lambda f, t: (f, 0, 0)
        ),
        out_shape=_out_sds(
            (n_ftiles, n_nodes * _C, fb * n_bins1), jnp.float32, vma),
        interpret=interpret,
    )(jmod, bins_fm, nodes[:, None], vals)

    # [Ft, K*C, Fb*B1] -> [K, F, B1, 3]
    out = out.reshape(n_ftiles, n_nodes, _C, fb, n_bins1)
    out = jnp.transpose(out, (1, 0, 3, 4, 2)).reshape(
        n_nodes, n_feat_p, n_bins1, _C
    )
    return out[:, :n_feat, :, :3]


# ---------------------------------------------------------------------------
# sorted tile-per-node kernel (deep levels)


def _hist_kernel(node_ref, first_ref, bins_ref, vals_ref, out_ref, *, n_feat, n_bins1):
    """One grid step = one row tile of one node.

    bins_ref: [R, F] int32 (VMEM); vals_ref: [R, C] f32 or bf16 (VMEM);
    out_ref:  [1, F, C, B1] f32 — the current node's slab (revisited across
    consecutive tiles of the same node).
    """
    t = pl.program_id(0)
    r = bins_ref.shape[0]
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (r, n_bins1), 1)
    vals = vals_ref[:]  # [R, C]; bf16 mode: both matmul operands bf16

    slabs = []
    for f in range(n_feat):
        b = bins_ref[:, f]
        onehot = (iota_b == b[:, None]).astype(vals.dtype)  # [R, B1]
        # [C, B1] = valsᵀ[C, R] @ onehot[R, B1]  (contraction over rows)
        h_f = jax.lax.dot_general(
            vals, onehot, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        slabs.append(h_f)
    slab = jnp.stack(slabs, axis=0)[None]  # [1, F, C, B1]

    first = first_ref[t] == 1

    @pl.when(first)
    def _():
        out_ref[...] = slab

    @pl.when(jnp.logical_not(first))
    def _():
        out_ref[...] = out_ref[...] + slab


def _code_words(n_bins1: int, n_feat: int):
    """(bits a code, codes a word, words a row) of the packed row: the bit
    width of ``n_bins1`` itself, so that value is a spare code."""
    bits = n_bins1.bit_length()
    per = 32 // bits
    return bits, per, -(-n_feat // per)


def _pack_row(bins, g, h, w, n_bins1: int, dtype):
    """[N, F] codes and g, h, w -> [N, P] int32, the row the preparation
    gathers. The chip prices a row gather by the row and by each group of 8
    int32 columns of it (36-49 ms a group at 6.26M rows on a v5e), so the
    row is made narrow: codes share a word, ``32 // bits`` of them at the bit
    width of ``n_bins1`` (whose value itself, which matches no bin, stands
    for any code out of range: such a code still adds nothing); bfloat16 g
    and h share one word and w takes one, float32 operands one each."""
    F = bins.shape[1]
    bits = _code_words(n_bins1, F)[0]
    u32 = jnp.uint32
    b = bins.astype(jnp.int32)
    b = jnp.where((b >= 0) & (b < n_bins1), b, n_bins1).astype(u32)
    words = pack_words((b[:, c] for c in range(F)), (bits,) * F)
    if dtype == jnp.bfloat16:
        def u16(x):
            return jax.lax.bitcast_convert_type(
                x.astype(jnp.bfloat16), jnp.uint16).astype(u32)
        words += [u16(g) | (u16(h) << 16), u16(w)]
    else:
        words += [jax.lax.bitcast_convert_type(x.astype(jnp.float32), u32)
                  for x in (g, h, w)]
    return jax.lax.bitcast_convert_type(jnp.stack(words, axis=1), jnp.int32)


def _unpack_row(rows, n_feat: int, n_bins1: int, dtype):
    """Inverse of ``_pack_row`` on gathered rows: [T*R, P] int32 ->
    (codes [T*R, F] int32, vals [T*R, C] ``dtype`` of (g, h, w, 0)), bit
    for bit."""
    bits, _, n_words = _code_words(n_bins1, n_feat)
    u = jax.lax.bitcast_convert_type(rows, jnp.uint32)
    codes = jnp.stack(unpack_words(lambda i: u[:, i], (bits,) * n_feat),
                      axis=1).astype(jnp.int32)
    v = u[:, n_words:]
    if dtype == jnp.bfloat16:
        def bf16(x):
            return jax.lax.bitcast_convert_type(
                x.astype(jnp.uint16), jnp.bfloat16)
        cols = [bf16(v[:, 0] & 0xFFFF), bf16(v[:, 0] >> 16),
                bf16(v[:, 1] & 0xFFFF)]
    else:
        cols = [jax.lax.bitcast_convert_type(v[:, i], jnp.float32)
                for i in range(3)]
    return codes, jnp.stack(cols + [jnp.zeros_like(cols[0])], axis=1)


def _prep_gathered(bins, nodes, g, h, n_nodes: int, n_bins1: int,
                   row_tile: int, t_max: int, rw=None, dtype=jnp.float32):
    """Operands of the sorted kernel in the node-padded layout (each node's
    rows in stable order, padded to a row_tile multiple, at least one tile
    a node), built by gathers alone: every destination row reads its source.

    One key-value sort gives the sorted node ids and the row order; the
    per-node offsets are a binary search in the sorted ids (no bincount:
    that is a scatter-add); per TILE, not per row, small-table lookups give
    its node, the position in ``order`` where its rows start and where the
    node's rows end. A tile's R source rows are R consecutive entries of
    ``order`` (one slice a tile), and ONE row gather moves codes and g, h, w
    together (``_pack_row``). A pad row reads whatever row follows in
    ``order`` (the next node's, or an inactive one: distinct rows gather
    faster than one row repeated) and only its g, h, w are zeroed, so it
    adds nothing whatever its codes; inactive rows (node < 0) sort past the
    last node and are never a tile's valid row, so g, h need no mask.

    Returns (bins_p [T*R, F] int32, vals_p [T*R, C] ``dtype``,
    item_node [T] int32 — dummy slot n_nodes for unused tiles —,
    item_first [T] int32).
    """
    n, n_feat = bins.shape
    r = row_tile
    i32 = jnp.int32
    # inactive rows (node < 0) -> dummy node n_nodes, past every tile's limit
    nd = jnp.where(nodes >= 0, nodes, n_nodes).astype(i32)
    nd_s, order = jax.lax.sort(
        (nd, jnp.arange(n, dtype=i32)), num_keys=1, is_stable=True)
    sort_off = jnp.searchsorted(
        nd_s, jnp.arange(n_nodes + 1, dtype=i32), side="left").astype(i32)
    counts = sort_off[1:] - sort_off[:-1]
    # every node gets >= 1 tile so empty nodes' slabs are zero-initialized,
    # never left undefined
    tiles = jnp.maximum((counts + r - 1) // r, 1)
    tile_off = jnp.concatenate([jnp.zeros((1,), i32), jnp.cumsum(tiles)])

    # tile t belongs to the node whose run of tiles contains it
    t = jnp.arange(t_max, dtype=i32)
    item_node = jnp.searchsorted(tile_off[1:], t, side="right").astype(i32)
    item_node = jnp.minimum(item_node, n_nodes)  # trailing unused tiles -> dummy slab
    item_first = jnp.concatenate(
        [jnp.ones((1,), i32), (item_node[1:] != item_node[:-1]).astype(i32)]
    )
    node_t = jnp.minimum(item_node, n_nodes - 1)
    base = sort_off[node_t] + (t - tile_off[node_t]) * r
    limit = jnp.where(item_node < n_nodes, sort_off[node_t + 1], 0)
    valid = jnp.arange(r, dtype=i32)[None, :] < (limit - base)[:, None]

    # a tile's window of ``order``; r entries of slack so that the last
    # node's last tile may start less than r short of the end
    order_p = jnp.concatenate([order, jnp.zeros((r,), i32)])
    src = jax.lax.gather(
        order_p, jnp.minimum(base, n)[:, None],
        jax.lax.GatherDimensionNumbers(
            offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,)),
        (r,), mode="promise_in_bounds",
    ).reshape(t_max * r)

    w = jnp.ones_like(g) if rw is None else rw
    rows = _pack_row(bins, g, h, w, n_bins1, dtype)
    bins_p, vals_p = _unpack_row(
        rows.at[src].get(mode="promise_in_bounds"), n_feat, n_bins1, dtype)
    vals_p = jnp.where(
        valid.reshape(t_max * r, 1), vals_p, jnp.zeros((), dtype))
    return bins_p, vals_p, item_node, item_first


_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


# ---------------------------------------------------------------------------
# frontier kernel (levels past the node ladder: many small nodes)
#
# A frontier level has up to hundreds of thousands of nodes of a few rows
# each, and each node is histogrammed over its own m features. Padding every
# node to a row tile (the sorted kernel) would move tens of times the rows.
# Here the rows are sorted by slot and the slots are cut into groups of
# ``_FRONTIER_SLOTS``; only a group is padded to a whole number of tiles, so
# the padding is at most a tile a group, whatever the nodes. A tile's rows
# all belong to one group, and its [group slots, m * B1] partial histogram of
# each channel is one MXU product of the tile-local one-hot of (slot) and
# the one-hot of (feature j, bin), accumulated in VMEM across the group's
# tiles.

#: slots of one group: the sublane extent of a tile's one-hot of slots
_FRONTIER_SLOTS = 256


def _frontier_kernel(blk_ref, first_ref, jmod_ref, lslot_ref, codes_ref, vals_ref,
                     out_ref, oh_ref, *, m, n_bins1, width):
    """One grid step = one row tile of one group of slots.

    jmod_ref: [B1, 1] f32 bin iota; lslot_ref: [1, R] int32, the row's slot
    within the group (-1: a pad row); codes_ref: [m, R] int32, the row's
    codes of its node's m features; vals_ref: [C, R] (g, h, w, 0);
    out_ref: [1, 3, W, m*B1] f32, the group's histogram (revisited across
    the group's tiles)."""
    t = pl.program_id(0)
    r = codes_ref.shape[1]
    dtype = vals_ref.dtype
    codes = codes_ref[...].astype(jnp.float32)  # codes <= 256 are exact
    jm = jmod_ref[...]
    for j in range(m):
        oh_ref[j * n_bins1:(j + 1) * n_bins1, :] = (
            jm == codes[j][None, :]).astype(dtype)
    onehot = oh_ref[...]  # [m*B1, R]
    sel = jax.lax.broadcasted_iota(jnp.int32, (width, r), 0) == lslot_ref[...]
    # the select in float32, whose layout is the mask's; the product's
    # operand is then cast (a bfloat16 select would relayout the mask)
    vals = vals_ref[...].astype(jnp.float32)
    slabs = []
    for c in range(3):
        a = jnp.where(sel, vals[c][None, :], 0.0).astype(dtype)  # [W, R]
        slabs.append(jax.lax.dot_general(
            a, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32))  # [W, m*B1]
    slab = jnp.stack(slabs)[None]

    @pl.when(first_ref[t] == 1)
    def _():
        out_ref[...] = slab

    @pl.when(first_ref[t] != 1)
    def _():
        out_ref[...] = out_ref[...] + slab


def _prep_frontier(codes, slots, g, h, n_slots: int, n_bins1: int, row_tile: int,
                   width: int, t_max: int, rw=None, dtype=jnp.float32):
    """Operands of the frontier kernel: the rows sorted by slot, each group
    of ``width`` slots padded to whole tiles (at least one: a group's
    histogram is then always written). The narrow packed row
    (``_pack_row``) is the sort's payload, so it reaches its sorted place
    with its key, and ONE gather of (key, packed row) at every tile row's
    sorted position lays out the tiles. Returns (lslot [1, T*R], codes
    [m, T*R], vals [C, T*R], item_group [T] — group count for unused
    tiles —, item_first [T])."""
    n, m = codes.shape
    r = row_tile
    i32 = jnp.int32
    nb = -(-n_slots // width)
    past = nb * width  # the key of a row with no slot: past every group
    key = jnp.where((slots >= 0) & (slots < n_slots), slots, past).astype(i32)
    w = jnp.ones_like(g) if rw is None else rw
    rows = _pack_row(codes, g, h, w, n_bins1, dtype)
    # stable: a slot's rows keep their order in the frame, so the kernel
    # sums each slot's rows in one fixed order
    key_s, *rows_s = jax.lax.sort(
        (key,) + tuple(rows[:, i] for i in range(rows.shape[1])), num_keys=1,
        is_stable=True)
    blk_off = jnp.searchsorted(key_s, jnp.arange(nb + 1, dtype=i32) * width,
                               side="left").astype(i32)
    tiles = jnp.maximum((blk_off[1:] - blk_off[:-1] + r - 1) // r, 1)
    tile_off = jnp.concatenate([jnp.zeros((1,), i32), jnp.cumsum(tiles)])
    t = jnp.arange(t_max, dtype=i32)
    item = jnp.minimum(jnp.searchsorted(tile_off[1:], t, side="right").astype(i32), nb)
    item_first = jnp.concatenate([jnp.ones((1,), i32), (item[1:] != item[:-1]).astype(i32)])
    grp = jnp.minimum(item, nb - 1)
    start = blk_off[grp] + (t - tile_off[grp]) * r
    limit = jnp.where(item < nb, blk_off[grp + 1], 0)
    lane = jnp.arange(r, dtype=i32)[None, :]
    valid = lane < (limit - start)[:, None]
    # every tile row's sorted position, and from it the row and its slot by
    # ONE gather: windows of the sorted rows a tile (lax.gather of slices)
    # would lower to a loop of a dynamic-slice a tile, ~17,000 a level at 8M
    # rows, each an event of a profile
    at = jnp.minimum(start[:, None] + lane, n - 1).reshape(t_max * r)
    got = jnp.stack([key_s] + rows_s, axis=1).at[at].get(mode="promise_in_bounds")
    lslot = jnp.where(valid, got[:, 0].reshape(t_max, r) - grp[:, None] * width, -1)
    codes_p, vals_p = _unpack_row(got[:, 1:], m, n_bins1, dtype)
    vals_p = jnp.where(valid.reshape(t_max * r, 1), vals_p, jnp.zeros((), dtype))
    return (lslot.reshape(1, t_max * r).astype(i32), codes_p.T, vals_p.T,
            item, item_first)


def build_frontier_histogram_pallas(codes, slots, g, h, n_slots: int, n_bins1: int,
                                    interpret: bool = False, vma: tuple = (),
                                    rw=None, dtype: str = "auto"):
    """A frontier level's histogram: [n_slots, m, n_bins1, 3] float32 of
    (Σg, Σh, Σw), a row adding to slot ``slots[i]`` (``n_slots``: none) at
    (j, codes[i, j]). bf16 operands on a TPU, f32 interpreted
    (``_kernel_choice``)."""
    dtype = _kernel_choice("sorted", dtype, 0)[1]
    return _build_frontier_pallas_jit(codes, slots, g, h, rw, n_slots, n_bins1,
                                      interpret, vma, dtype)


@partial(jax.jit, static_argnames=("n_slots", "n_bins1", "interpret", "vma", "dtype"))
def _build_frontier_pallas_jit(codes, slots, g, h, rw, n_slots: int, n_bins1: int,
                               interpret: bool, vma: tuple, dtype: str):
    n, m = codes.shape
    r, width = _ROW_TILE, _FRONTIER_SLOTS
    nb = -(-n_slots // width)
    t_max = -(-n // r) + nb
    with jax.named_scope("frontier_prep"):
        lslot, codes_p, vals_p, item, item_first = _prep_frontier(
            codes, slots, g, h, n_slots, n_bins1, r, width, t_max, rw=rw,
            dtype=_DTYPES[dtype])
    jmod = jnp.asarray(np.arange(n_bins1)[:, None], dtype=jnp.float32)
    if vma:
        jmod = jax.lax.pcast(jmod, tuple(vma), to="varying")
    mb = m * n_bins1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t_max,),
        in_specs=[
            pl.BlockSpec((n_bins1, 1), lambda t, b, f: (0, 0)),
            pl.BlockSpec((1, r), lambda t, b, f: (0, t)),
            pl.BlockSpec((m, r), lambda t, b, f: (0, t)),
            pl.BlockSpec((_C, r), lambda t, b, f: (0, t)),
        ],
        out_specs=pl.BlockSpec((1, 3, width, mb), lambda t, b, f: (b[t], 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((mb, r), _DTYPES[dtype])],
    )
    out = pl.pallas_call(
        partial(_frontier_kernel, m=m, n_bins1=n_bins1, width=width),
        grid_spec=grid_spec,
        out_shape=_out_sds((nb + 1, 3, width, mb), jnp.float32, vma),
        interpret=interpret,
    )(item, item_first, jmod, lslot, codes_p, vals_p)
    # [groups, 3, W, m*B1] -> [slots, m, B1, 3]
    out = out[:nb].reshape(nb, 3, width, m, n_bins1)
    out = jnp.transpose(out, (0, 2, 3, 4, 1)).reshape(nb * width, m, n_bins1, 3)
    return out[:n_slots]


def _kernel_choice(kernel: str, dtype: str, n_nodes: int):
    """The plan of one level, (kernel, operand precision), decided here and
    nowhere else, from what the code can observe. ``auto`` (what every fit
    asks for) is the node-matmul kernel while its all-nodes output fits
    VMEM and the sorted tile-per-node kernel past that; bf16 operands on a
    real TPU (2x MXU rate, halved VMEM traffic; accumulation is always f32)
    and f32 where the kernels are interpreted (the CPU interpreter path
    doubles as the exact-parity oracle). A name asked for is kept."""
    if kernel == "auto":
        kernel = (
            "nodematmul" if n_nodes * _C <= _NODE_MATMUL_MAX_KC else "sorted")
    if kernel not in ("nodematmul", "sorted"):
        raise ValueError(
            f"hist kernel must be 'nodematmul' or 'sorted', got {kernel!r}")
    if dtype == "auto":
        dtype = "bf16" if jax.default_backend() == "tpu" else "f32"
    if dtype not in _DTYPES:
        raise ValueError(f"hist dtype must be 'f32' or 'bf16', got {dtype!r}")
    return kernel, dtype


def build_histogram_pallas(
    bins, nodes, g, h, n_nodes: int, n_bins1: int,
    row_tile: int = None, interpret: bool = False, vma: tuple = (),
    kernel: str = "auto", bins_fm=None, rw=None, dtype: str = "auto",
):
    """Drop-in Pallas replacement for ``histogram._shard_histogram``.

    bins: [N, F] int bin codes (NA bucket = n_bins1 - 1 handled upstream);
    nodes: [N] int32 (-1 = inactive row); g, h: [N] float; rw: optional [N]
    per-row count weight (weights_column -> the count channel reports Σw).
    kernel: 'nodematmul' | 'sorted' | 'auto'; dtype: 'f32' | 'bf16' |
    'auto' — matmul operand precision (the one-hot mask is exact either
    way; bf16 rounds g/h/w inputs to 8 mantissa bits, accumulation stays
    f32). ``_kernel_choice`` resolves both.
    Returns [n_nodes, F, n_bins1, 3] float32 of (Σg, Σh, Σw).

    K class trees: nodes, g, h [K, N], a row's node and channels in each
    tree; returns [K * n_nodes, F, n_bins1, 3], class-major. While the
    node-matmul kernel holds the K * n_nodes slots (``_kernel_choice``) they
    are ONE launch (``_build_classes_nodematmul``), past it a launch a class
    (``histogram.class_launches`` states which).
    """
    if nodes.ndim == 2:
        # K class trees: one launch over their (class, node) slots while the
        # node-matmul kernel holds them all, else a launch a class
        n_classes = nodes.shape[0]
        if _kernel_choice(kernel, dtype, n_classes * n_nodes)[0] != "nodematmul":
            return jnp.concatenate([build_histogram_pallas(
                bins, nodes[c], g[c], h[c], n_nodes, n_bins1, row_tile, interpret,
                vma, kernel, bins_fm, rw, dtype) for c in range(n_classes)])
        n_nodes = n_classes * n_nodes
    kernel, dtype = _kernel_choice(kernel, dtype, n_nodes)
    # the scope sits OUTSIDE the jit: XLA names the kernel's custom-call
    # after the innermost component of its name stack, and the benchmark's
    # accepted readers find the kernels by ``_build_histogram_pallas_jit``
    with jax.named_scope("hist_" + kernel):
        return _build_histogram_pallas_jit(
            bins, nodes, g, h, n_nodes, n_bins1, row_tile, interpret,
            vma, kernel, bins_fm, rw, dtype,
        )


@partial(
    jax.jit,
    static_argnames=(
        "n_nodes", "n_bins1", "row_tile", "interpret", "vma", "kernel", "dtype"
    ),
)
def _build_histogram_pallas_jit(
    bins, nodes, g, h, n_nodes: int, n_bins1: int,
    row_tile, interpret: bool, vma: tuple,
    kernel: str, bins_fm, rw, dtype: str,
):
    if nodes.ndim == 2:
        # K class trees (``build_histogram_pallas``): n_nodes counts the
        # slots of all of them
        kernel, dtype = _kernel_choice(kernel, dtype, n_nodes)
        return _build_classes_nodematmul(
            bins, nodes, g, h, n_nodes // nodes.shape[0], n_bins1,
            row_tile=row_tile or _ROW_TILE, feat_block=_FEAT_BLOCK,
            interpret=interpret, vma=vma, bins_fm=bins_fm, rw=rw,
            dtype=_DTYPES[dtype],
        )
    kernel, dtype = _kernel_choice(kernel, dtype, n_nodes)
    if kernel == "nodematmul":
        return _build_histogram_nodematmul(
            bins, nodes, g, h, n_nodes, n_bins1,
            row_tile=row_tile or _ROW_TILE, feat_block=_FEAT_BLOCK,
            interpret=interpret, vma=vma, bins_fm=bins_fm, rw=rw,
            dtype=_DTYPES[dtype],
        )
    n, n_feat = bins.shape
    r = row_tile or 512  # sorted kernel keeps its original tile height
    t_max = (n + r - 1) // r + n_nodes  # ≤ R-1 pad rows per node

    with jax.named_scope("sorted_prep"):
        bins_p, vals_p, item_node, item_first = _prep_gathered(
            bins, nodes, g, h, n_nodes, n_bins1, r, t_max, rw=rw,
            dtype=_DTYPES[dtype],
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t_max,),
        in_specs=[
            pl.BlockSpec((r, n_feat), lambda t, nref, fref: (t, 0)),
            pl.BlockSpec((r, _C), lambda t, nref, fref: (t, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, n_feat, _C, n_bins1), lambda t, nref, fref: (nref[t], 0, 0, 0)
        ),
    )

    out = pl.pallas_call(
        partial(_hist_kernel, n_feat=n_feat, n_bins1=n_bins1),
        grid_spec=grid_spec,
        # slab n_nodes is the dummy for trailing all-pad tiles; vma marks the
        # per-shard output as varying over the mesh axes when called inside
        # shard_map (each shard builds its private histogram pre-psum)
        out_shape=_out_sds(
            (n_nodes + 1, n_feat, _C, n_bins1), jnp.float32, vma),
        interpret=interpret,
    )(item_node, item_first, bins_p, vals_p)

    # [K, F, C, B1] -> [K, F, B1, 3] to match the XLA oracle layout
    return jnp.transpose(out[:n_nodes], (0, 1, 3, 2))[..., :3]
