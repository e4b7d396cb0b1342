"""Chunk codec layer (h2o3_tpu/frame/codecs.py).

The contract under test: a codec is selected for a column-chunk only if
a literal encode→decode round-trip reproduces the dense payload
bit-exactly (uint64 views), so decoding never changes a result anywhere
— NaN payload bits, signed zeros, denormals and int-boundary floats
either survive exactly or the chunk stays dense.  Group homogenization
(group_rep) and codec-aware rollups (payload_rollups) must uphold the
same contract, and a chunk-homed parse with codecs enabled must
materialize bit-identically to the same parse with H2O3_TPU_CODECS=0.
"""

import numpy as np
import pytest

from h2o3_tpu.frame import codecs
from h2o3_tpu.frame.frame import NA_CAT, ColType, Column
from h2o3_tpu.frame.parse import parse_csv
from h2o3_tpu.frame.rollups import compute_rollups, payload_rollups
from h2o3_tpu.util import telemetry

DENORM = 5e-324  # smallest positive subnormal


def _bits(x):
    return np.ascontiguousarray(np.asarray(x, dtype=np.float64)).view(
        np.uint64)


def _enc(x):
    """Encoded payload of one numeric column-chunk."""
    x = np.asarray(x, dtype=np.float64)
    return codecs.encode_chunk([int(x.size), [x], False])[1][0]


def _codec_of(payload):
    return payload["c"] if codecs.is_encoded(payload) else "dense"


def _rng(seed=7):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# property-style special-value matrix: encode→decode is uint64-identical
# (or the chunk legitimately stayed dense, which is identity for free)

SPECIALS = {
    "all_nan": np.full(64, np.nan),
    "all_pos_zero": np.zeros(64),
    "all_neg_zero": np.full(64, -0.0),
    "signed_zero_mix": np.where(np.arange(64) % 2 == 0, 0.0, -0.0),
    "const_pi": np.full(100, np.pi),
    "single_value": np.array([42.0]),
    "single_nan": np.array([np.nan]),
    "denormals": np.array([DENORM, -DENORM, 0.0, -0.0, 2 * DENORM] * 8),
    "inf_mix": np.array([np.inf, -np.inf, 0.0, 1.5, np.nan] * 10),
    "int_boundary": np.array(
        [2.0**53, 2.0**53 - 1, -(2.0**53), 2.0**31, -(2.0**31) - 1] * 5),
    "small_ints_with_na": np.where(
        np.arange(200) % 13 == 0, np.nan, np.arange(200) % 97),
    "quarter_steps": np.arange(300) * 0.25 - 20.0,
    "mostly_zero": np.where(np.arange(500) % 83 == 0, 3.75, 0.0),
    "few_uniq_irrational": _rng().choice(
        [np.pi, np.e, np.sqrt(2), -np.pi / 3, 1 / 3], size=400),
    "f32_exact": _rng(3).standard_normal(300).astype(
        np.float32).astype(np.float64),
    "random_dense": _rng(5).standard_normal(256),
    "huge_magnitudes": np.array([1e300, -1e300, 1e-300, -1e-300] * 8),
    "empty": np.empty(0),
}


@pytest.mark.parametrize("name", sorted(SPECIALS))
def test_roundtrip_bit_identity(name):
    x = np.asarray(SPECIALS[name], dtype=np.float64)
    value = codecs.encode_chunk([int(x.size), [x.copy()], False])
    back = codecs.decode_chunk(value)[1][0]
    back = np.asarray(back, dtype=np.float64)
    assert back.shape == x.shape
    assert np.array_equal(_bits(back), _bits(x)), name


def test_selection_picks_expected_codecs():
    assert _codec_of(_enc(np.full(512, 7.5))) == "const"
    assert _codec_of(_enc(np.full(512, np.nan))) == "const"
    assert _codec_of(_enc(SPECIALS["mostly_zero"])) == "sparse"
    assert _codec_of(_enc(SPECIALS["small_ints_with_na"])) == "affine"
    assert _codec_of(_enc(SPECIALS["quarter_steps"])) == "affine"
    assert _codec_of(_enc(SPECIALS["few_uniq_irrational"])) == "dict"
    assert _codec_of(_enc(_rng(3).standard_normal(4096).astype(
        np.float32).astype(np.float64))) == "f32"
    # all-unique random f64: no candidate beats dense
    assert _codec_of(_enc(_rng(5).standard_normal(4096))) == "dense"


def test_affine_na_sentinel_is_reserved():
    p = _enc(SPECIALS["small_ints_with_na"])
    assert p["c"] == "affine"
    sent = int(np.iinfo(p["codes"].dtype).max)
    na_rows = np.isnan(SPECIALS["small_ints_with_na"])
    assert np.array_equal(p["codes"] == sent, na_rows)
    # a domain that needs the all-ones code cannot pack into that dtype
    full = np.arange(256, dtype=np.float64)  # 0..255 needs code 255
    pf = _enc(full)
    if codecs.is_encoded(pf) and pf["c"] == "affine":
        assert pf["codes"].dtype == np.uint16


def test_encode_is_idempotent_and_metered():
    c = telemetry.REGISTRY.get("chunk_codec_total")
    before = float(c.value(codec="const"))
    x = np.full(128, 2.5)
    v1 = codecs.encode_chunk([128, [x], False])
    assert float(c.value(codec="const")) == before + 1
    v2 = codecs.encode_chunk(v1)  # already encoded: pass-through, unmetered
    assert v2[1][0] is v1[1][0]
    assert float(c.value(codec="const")) == before + 1
    g = telemetry.REGISTRY.get("chunk_resident_bytes")
    assert float(g.value(codec="const")) > 0


def test_kill_switch_lands_dense(monkeypatch):
    monkeypatch.setenv("H2O3_TPU_CODECS", "0")
    v = codecs.encode_chunk([128, [np.full(128, 2.5)], False])
    assert not codecs.is_encoded_chunk(v)
    assert isinstance(v[1][0], np.ndarray)


def test_min_ratio_rejects_marginal_wins(monkeypatch):
    x = _rng(3).standard_normal(512).astype(np.float32).astype(np.float64)
    assert _codec_of(_enc(x)) == "f32"  # 0.5x dense, under the default 0.75
    monkeypatch.setenv("H2O3_TPU_CODEC_MIN_RATIO", "0.4")
    assert _codec_of(_enc(x)) == "dense"


def test_encoded_nbytes_reports_packed_size():
    x = np.where(np.arange(4096) % 83 == 0, 3.75, 0.0)
    enc = codecs.encode_chunk([x.size, [x.copy()], False])
    dense = [x.size, [x], False]
    assert codecs.encoded_nbytes(enc) < 0.1 * codecs.encoded_nbytes(dense)


def test_cat_roundtrip_long_domain():
    n, levels = 1000, 300
    codes = (np.arange(n) % levels).astype(np.int32)
    codes[::37] = NA_CAT
    domain = [f"lv{i:04d}" for i in range(levels)]
    v = codecs.encode_chunk([n, [(codes.copy(), list(domain))], False])
    p = v[1][0]
    assert codecs.is_encoded(p) and p["c"] == "catpack"
    assert p["codes"].dtype == np.uint16  # 300 levels outgrow uint8
    back_codes, back_domain = codecs.decode_column(p)
    assert np.array_equal(back_codes, codes)
    assert back_domain == domain


def test_str_roundtrip_dictionary():
    vals = ["alpha", "beta", "gamma", None, "alpha"] * 200
    arr = np.empty(len(vals), dtype=object)
    arr[:] = vals
    v = codecs.encode_chunk([arr.size, [arr], False])
    p = v[1][0]
    assert codecs.is_encoded(p) and p["c"] == "strdict"
    back = codecs.decode_column(p)
    assert all(a == b for a, b in zip(back, arr))


# ---------------------------------------------------------------------------
# group homogenization: regrouping must re-verify the chunk contract


def _group_case(chunks):
    payloads = [_enc(c) for c in chunks]
    full = np.concatenate([np.asarray(c, dtype=np.float64) for c in chunks])
    return payloads, full


def _rep_decode(rep):
    kind = rep[0]
    if kind == "const":
        return np.repeat(rep[1], rep[2])
    if kind == "affine":
        codes, off, scale, sent = rep[1], rep[2], rep[3], rep[4]
        out = off + codes.astype(np.float64) * scale
        out[codes == sent] = np.nan
        return out
    if kind == "dict":
        return rep[2][rep[1]]
    if kind == "f32":
        return np.asarray(rep[1]).astype(np.float64)
    return np.asarray(rep[1], dtype=np.float64)


GROUP_CASES = {
    "all_const": [np.full(50, 1.25), np.full(70, 1.25)],
    "const_mismatch": [np.full(50, 1.25), np.full(70, 2.5)],
    "affine_shared_scale": [np.arange(100, 150, dtype=np.float64),
                            np.arange(400, 420, dtype=np.float64)],
    "affine_with_na": [
        np.where(np.arange(120) % 11 == 0, np.nan,
                 np.arange(120, dtype=np.float64)),
        np.arange(60, dtype=np.float64) + 500.0],
    "affine_mixed_scale": [np.arange(80) * 0.5, np.arange(80) * 0.25],
    "all_f32": [_rng(1).standard_normal(90).astype(np.float32).astype(
        np.float64), _rng(2).standard_normal(40).astype(
        np.float32).astype(np.float64)],
    "mixed_enc_dense": [np.full(50, 3.0), _rng(9).standard_normal(128)],
    "sparse_plus_const": [np.where(np.arange(400) % 97 == 0, 2.0, 0.0),
                          np.zeros(100)],
}


@pytest.mark.parametrize("name", sorted(GROUP_CASES))
def test_group_rep_bit_identity(name):
    payloads, full = _group_case(GROUP_CASES[name])
    rep = codecs.group_rep(payloads)
    back = _rep_decode(rep)
    assert back.shape == full.shape
    assert np.array_equal(_bits(back), _bits(full)), (name, rep[0])


def test_group_rep_shapes():
    payloads, _ = _group_case(GROUP_CASES["all_const"])
    assert codecs.group_rep(payloads)[0] == "const"
    payloads, _ = _group_case(GROUP_CASES["affine_shared_scale"])
    assert codecs.group_rep(payloads)[0] == "affine"
    payloads, _ = _group_case(GROUP_CASES["all_f32"])
    assert codecs.group_rep(payloads)[0] == "f32"
    payloads, _ = _group_case(GROUP_CASES["mixed_enc_dense"])
    assert codecs.group_rep(payloads)[0] == "dense"
    # heterogeneous affine scales fall through to the dict union
    payloads, _ = _group_case(GROUP_CASES["affine_mixed_scale"])
    assert codecs.group_rep(payloads)[0] in ("dict", "dense")


def test_group_rep_device_parity_affine():
    """The fused program's decode (offset + code*scale as two f64 ops,
    sentinel → NaN) matches the host decode bit-for-bit on device."""
    import jax
    import jax.numpy as jnp

    payloads, full = _group_case(GROUP_CASES["affine_with_na"])
    rep = codecs.group_rep(payloads)
    assert rep[0] == "affine"
    _, codes, off, scale, sent = (rep[0], rep[1], rep[2], rep[3], rep[4])
    with jax.enable_x64():
        x = jnp.asarray(off) + jnp.asarray(codes).astype(
            jnp.float64) * jnp.asarray(scale)
        dev = np.asarray(
            jnp.where(jnp.asarray(codes) == sent, jnp.nan, x))
    assert np.array_equal(_bits(dev), _bits(full))


# ---------------------------------------------------------------------------
# codec-aware rollups: exact where promised, moment-merge where streamed


ROLLUP_CASES = {
    "mixed_codecs": [np.full(64, 4.0),
                     np.where(np.arange(300) % 83 == 0, 3.75, 0.0),
                     np.where(np.arange(200) % 13 == 0, np.nan,
                              np.arange(200) % 97),
                     _rng(4).standard_normal(150)],
    "all_na": [np.full(30, np.nan), np.full(20, np.nan)],
    "single_chunk_int": [np.arange(500, dtype=np.float64)],
    "with_inf": [np.array([np.inf, -np.inf, 1.0, np.nan] * 25)],
}


@pytest.mark.parametrize("name", sorted(ROLLUP_CASES))
def test_payload_rollups_matches_dense(name):
    chunks = ROLLUP_CASES[name]
    payloads = [_enc(c) for c in chunks]
    full = np.concatenate([np.asarray(c, dtype=np.float64) for c in chunks])
    got = payload_rollups(payloads)
    ref = compute_rollups(Column("x", full.copy(), ColType.NUM))
    # exact fields
    assert got.na_count == ref.na_count
    assert got.zero_count == ref.zero_count
    assert got.is_int == ref.is_int
    assert np.array_equal(_bits([got.min]), _bits([ref.min]))
    assert np.array_equal(_bits([got.max]), _bits([ref.max]))
    # streamed moments: merged per-chunk, final-ulp tolerance only
    if np.isnan(ref.mean):
        assert np.isnan(got.mean)
    else:
        np.testing.assert_allclose(got.mean, ref.mean, rtol=1e-12, atol=0)
        np.testing.assert_allclose(got.sigma, ref.sigma, rtol=1e-9,
                                   atol=1e-300)


# ---------------------------------------------------------------------------
# cluster: a chunk-homed parse with codecs on materializes bit-identically
# to the same parse with H2O3_TPU_CODECS=0 and to the serial parser


def _mixed_csv(n=3000):
    rng = np.random.default_rng(17)
    dense = rng.standard_normal(n)
    lines = ["ints,const,sparse,dense,cat"]
    for i in range(n):
        iv = "" if i % 13 == 0 else str(i % 97)
        sv = "3.75" if i % 83 == 0 else "0"
        lines.append(
            f"{iv},7.5,{sv},{dense[i]!r},lv{i % 5}")
    return "\n".join(lines) + "\n"


@pytest.mark.leaks_keys
def test_cluster_encoded_vs_dense_bit_identity(monkeypatch):
    from test_rapids_dist import _form_cloud, _parse_to_homes, _stop_all

    from h2o3_tpu.cluster.frames import chunk_key
    from h2o3_tpu.cluster.membership import set_local_cloud

    text = _mixed_csv()
    serial = parse_csv(text)
    clouds = _form_cloud(2, "cdx")
    set_local_cloud(clouds[0])
    try:
        enc = _parse_to_homes(clouds[0], "codec_parity_enc", text,
                              chunk_bytes=16384)
        g0 = enc.chunk_layout["groups"][0]
        v0 = clouds[0].dkv_store.get(chunk_key(g0["anchor"], int(g0["lo"])))
        assert codecs.is_encoded_chunk(v0), "parse landed dense payloads"
        assert enc.nbytes_wire > 0

        monkeypatch.setenv("H2O3_TPU_CODECS", "0")
        plain = _parse_to_homes(clouds[0], "codec_parity_plain", text,
                                chunk_bytes=16384)
        monkeypatch.delenv("H2O3_TPU_CODECS")
        # encoded replicas are smaller than dense ones for this mix
        assert enc.nbytes_wire < plain.nbytes_wire

        for name in serial.names:
            ref = serial.col(name)
            a, b = enc.col(name), plain.col(name)
            if ref.type in (ColType.STR, ColType.UUID):
                continue
            assert np.array_equal(_bits(a.numeric_view()),
                                  _bits(ref.numeric_view())), name
            assert np.array_equal(_bits(a.numeric_view()),
                                  _bits(b.numeric_view())), name
            if ref.type is ColType.CAT:
                assert a.domain == ref.domain

        # unmaterialized rollups off encoded payloads: exact fields agree
        enc2 = _parse_to_homes(clouds[0], "codec_parity_enc2", text,
                               chunk_bytes=16384)
        r = enc2.column_rollups("ints")
        rr = serial.col("ints").rollups
        assert (r.na_count, r.zero_count, r.min, r.max) == \
            (rr.na_count, rr.zero_count, rr.min, rr.max)
    finally:
        set_local_cloud(None)
        _stop_all(clouds)
