"""Per-kernel validation: Pallas (interpret=True) vs the ref.py oracles,
and the decode tile vs the jnp training chain, swept over shapes and
dtypes (the property-sweep substitute for hypothesis, which is
unavailable offline)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize(
    "b,s,hq,hkv,d",
    [(1, 128, 4, 4, 64), (2, 256, 8, 2, 64), (2, 128, 4, 1, 128)],
)
def test_flash_attention_sweep(b, s, hq, hkv, d):
    q = jnp.asarray(RNG.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), jnp.float32)
    want = ops.attention(q, k, v, impl="ref")
    got = ops.attention(q, k, v, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_attention_decode_offset():
    """Decode shape: 1 query attending a longer KV with causal offset."""
    b, skv, h, d = 2, 256, 4, 64
    q = jnp.asarray(RNG.normal(size=(b, 128, h, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, skv, h, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, skv, h, d)), jnp.float32)
    want = ops.attention(q, k, v, impl="ref", q_offset=128)
    got = ops.attention(q, k, v, impl="pallas_interpret", q_offset=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_chunked_attention_matches_ref():
    b, s, h, d = 2, 4096, 2, 32
    q = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    want = ref.mha_attention(q, k, v)
    got = ref.mha_attention_chunked(q, k, v, chunk=512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_kv_len_masking():
    b, sq, skv, h, d = 3, 1, 64, 2, 16
    q = jnp.asarray(RNG.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, skv, h, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, skv, h, d)), jnp.float32)
    kv_len = jnp.asarray([10, 32, 64], jnp.int32)
    out = ref.mha_attention(q, k, v, causal=False, kv_len=kv_len)
    # manual check for batch 0: only first 10 kv positions participate
    out0 = ref.mha_attention(q[:1], k[:1, :10], v[:1, :10], causal=False)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out0[0]), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# explicit-impl attention on non-aligned sequence lengths (pad + mask path)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
def test_attention_explicit_impl_odd_seq(causal):
    """seq=130 is not a multiple of the 128 tile: an EXPLICIT pallas impl
    must pad+mask and run the kernel, not silently fall back to ref."""
    b, s, h, d = 1, 130, 2, 64
    q = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    want = ops.attention(q, k, v, impl="ref", causal=causal)
    got = ops.attention(q, k, v, impl="pallas_interpret", causal=causal)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_attention_explicit_impl_odd_kv_only():
    """Cross-attention shape: aligned queries, ragged KV (skv=130)."""
    b, sq, skv, h, d = 1, 128, 130, 2, 64
    q = jnp.asarray(RNG.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, skv, h, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, skv, h, d)), jnp.float32)
    want = ops.attention(q, k, v, impl="ref")
    got = ops.attention(q, k, v, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_attention_ragged_tail(causal):
    """sq=2049 = 4*512 + 1: the scan covers the aligned prefix and the
    ragged tail is finished separately (no silent full-score fallback)."""
    b, s, h, d = 1, 2049, 2, 32
    q = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, h, d)), jnp.float32)
    want = ref.mha_attention(q, k, v, causal=causal)
    got = ref.mha_attention_chunked(q, k, v, chunk=512, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused NTTD decode tile: interpret-mode Pallas vs the jnp oracle
# ---------------------------------------------------------------------------
def _decode_tile_args(b, t, m, hid, rank, dtype, seed=1):
    rng = np.random.default_rng(seed)

    def mk(*shape, scale=0.3):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    idx = jnp.asarray(rng.integers(0, m, size=(b, t)), jnp.int32)
    return idx, (
        mk(t, m, hid),                              # emb
        mk(hid, 4 * hid), mk(hid, 4 * hid), mk(4 * hid, scale=0.1),  # lstm
        mk(hid, rank), mk(rank, scale=0.1),         # first head
        mk(hid, rank * rank, scale=0.5 / np.sqrt(rank)), mk(rank * rank, scale=0.1),
        mk(hid, rank), mk(rank, scale=0.1),         # last head
    )


def _training_chain(idx, ws):
    """``nttd.apply`` over the tile's operands: the per-step gather,
    ``ref.lstm_scan``, the three heads, ``ref.tt_contract``."""
    emb, wi, wh, b, wf, bf, wm, bm, wl, bl = ws
    t, rank = idx.shape[1], bf.shape[0]
    x = jnp.stack([emb[j][idx[:, j]] for j in range(t)], axis=1)
    hs = ref.lstm_scan(x, wi, wh, b)
    if t > 2:
        mids = (hs[:, 1:-1] @ wm + bm).reshape(-1, t - 2, rank, rank)
    else:
        mids = jnp.zeros((idx.shape[0], 0, rank, rank), emb.dtype)
    return ref.tt_contract(hs[:, 0] @ wf + bf, mids, hs[:, -1] @ wl + bl)


# (B, T, H, R) with the tolerance (f32, bf16) of the tile against the chain
CHAIN_ROWS = [
    ((32, 2, 16, 4), (1e-5, 0.15)),
    ((64, 7, 16, 8), (1e-5, 0.15)),
    ((100, 12, 16, 16), (1e-5, 0.15)),
    ((7, 5, 16, 8), (1e-5, 0.15)),
    ((256, 10, 16, 32), (1e-5, 0.15)),
    ((16, 6, 8, 8), (2e-5, 0.1)),
    ((50, 9, 16, 8), (2e-5, 0.1)),
    ((33, 12, 32, 8), (2e-5, 0.1)),
    ((8, 3, 64, 8), (2e-5, 0.1)),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("row", CHAIN_ROWS, ids=lambda r: "x".join(map(str, r[0])))
def test_decode_tile_matches_training_chain(row, dtype):
    """The served decode (the tile, interpreted here) is bit-identical to
    the jitted oracle, and both agree with the jnp chain that training
    runs."""
    (b, t, hid, rank), tols = row
    idx, ws = _decode_tile_args(b, t, 10, hid, rank, dtype)
    got = ops.nttd_decode_tile(idx, *ws, interpret=True)
    oracle = ops.nttd_decode_tile(idx, *ws)
    assert got.shape == (b,) and got.dtype == ws[0].dtype
    assert np.array_equal(np.asarray(got), np.asarray(oracle))
    tol = tols[0] if dtype == jnp.float32 else tols[1]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(_training_chain(idx, ws), np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize(
    "rank,t,dtype",
    [
        (r, t, dt)
        for r in [4, 8, 32]
        for t in [2, 3, 8]
        for dt in [jnp.float32, jnp.bfloat16]
    ],
)
def test_decode_tile_parity_sweep(rank, t, dtype):
    """Interpret-mode Pallas is BIT-IDENTICAL to the jitted oracle (same
    compiled op order), and within eager-vs-jit ulp noise of the eager
    oracle."""
    idx, ws = _decode_tile_args(32, t, 10, 16, rank, dtype)
    got = ops.nttd_decode_tile(idx, *ws, interpret=True, tile_b=16)
    fused = ops.nttd_decode_tile(idx, *ws)
    assert got.dtype == ws[0].dtype
    assert np.array_equal(np.asarray(got), np.asarray(fused)), (
        "interpret kernel drifted from jitted oracle"
    )
    eager = ref.nttd_decode_tile(idx, *ws)
    tol = 1e-5 if dtype == jnp.float32 else 0.1
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(eager, np.float32),
        rtol=tol, atol=tol,
    )


def test_decode_tile_non_multiple_batch():
    """b=33 with tile_b=16: the wrapper pads the batch to a tile multiple
    and slices the result back."""
    idx, ws = _decode_tile_args(33, 3, 7, 16, 8, jnp.float32)
    got = ops.nttd_decode_tile(idx, *ws, interpret=True, tile_b=16)
    fused = ops.nttd_decode_tile(idx, *ws)
    assert got.shape == (33,)
    assert np.array_equal(np.asarray(got), np.asarray(fused))


# the benchmark's widths: (H, R, M, T) of pems MEDIUM, nyc SMALL and the
# widest granite leaf (d' 17)
BENCH_WIDTHS = {"pems": (18, 10, 8, 10), "nyc": (12, 6, 16, 9), "granite": (16, 8, 16, 17)}


@pytest.mark.parametrize("batch", [1, 127, 300, 1000])
@pytest.mark.parametrize("widths", list(BENCH_WIDTHS), ids=str)
def test_decode_tile_at_bench_widths(widths, batch):
    """At each configuration's widths, batches that are not a multiple of
    128 lanes, with the tile width the wrapper picks: interpret mode is
    bit-identical to the jitted oracle and close to the training chain."""
    hid, rank, m, t = BENCH_WIDTHS[widths]
    idx, ws = _decode_tile_args(batch, t, m, hid, rank, jnp.float32)
    got = ops.nttd_decode_tile(idx, *ws, interpret=True)
    fused = ops.nttd_decode_tile(idx, *ws)
    assert got.shape == (batch,)
    assert np.array_equal(np.asarray(got), np.asarray(fused))
    np.testing.assert_allclose(np.asarray(got), np.asarray(_training_chain(idx, ws)),
                               rtol=1e-5, atol=1e-5)


def _pad_hidden(ws, hp):
    """The operands with zero hidden units appended up to ``hp``: zero
    embedding columns, zero gate rows and columns in each gate block, zero
    head rows."""
    emb, wi, wh, b, wf, bf, wm, bm, wl, bl = ws
    dh = hp - emb.shape[2]

    def gates(w):
        w = w.reshape(w.shape[0], 4, -1)
        return jnp.pad(w, ((0, dh), (0, 0), (0, dh))).reshape(hp, 4 * hp)

    rows = lambda w: jnp.pad(w, ((0, dh), (0, 0)))  # noqa: E731
    return (jnp.pad(emb, ((0, 0), (0, 0), (0, dh))), gates(wi), gates(wh),
            jnp.pad(b.reshape(4, -1), ((0, 0), (0, dh))).reshape(4 * hp),
            rows(wf), bf, rows(wm), bm, rows(wl), bl)


@pytest.mark.parametrize("widths", ["pems", "nyc"])
def test_decode_tile_padded_hidden_matches_unpadded(widths):
    """Hidden units padded with zeros to a multiple of 8 (pems 18 -> 24,
    nyc 12 -> 16) decode exactly what the unpadded operands decode: a
    padded unit's c and h stay 0."""
    hid, rank, m, t = BENCH_WIDTHS[widths]
    idx, ws = _decode_tile_args(300, t, m, hid, rank, jnp.float32)
    got = ops.nttd_decode_tile(idx, *_pad_hidden(ws, -(-hid // 8) * 8), interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(ops.nttd_decode_tile(idx, *ws)))


def test_decode_tile_empty_batch():
    idx, ws = _decode_tile_args(0, 3, 7, 16, 8, jnp.float32)
    for interpret in (True, False):
        out = ops.nttd_decode_tile(idx, *ws, interpret=interpret)
        assert out.shape == (0,)
        assert out.dtype == ws[0].dtype


def test_decode_tile_rejects_short_chain():
    idx, ws = _decode_tile_args(8, 2, 7, 16, 8, jnp.float32)
    with pytest.raises(ValueError, match="T >= 2"):
        ops.nttd_decode_tile(idx[:, :1], *(w if i else w[:1] for i, w in enumerate(ws)))


def test_fused_apply_matches_ref_apply():
    """The served decode (the tile over ``fused_decode_inputs``) matches
    ``nttd.apply``, the per-op chain that training runs."""
    import jax

    from repro.core import nttd
    from repro.core.folding import make_folding_spec

    spec = make_folding_spec((20, 18, 12))
    cfg = nttd.NTTDConfig(rank=6, hidden=12)
    params = nttd.init_params(jax.random.PRNGKey(3), spec, cfg)
    rng = np.random.default_rng(5)
    pos = jnp.asarray(
        np.stack([rng.integers(0, s, 257) for s in spec.shape], axis=1), jnp.int32
    )
    want = nttd.apply_at_positions(params, pos, spec, cfg)
    got = ops.nttd_decode_tile(spec.fold_indices(pos),
                               *nttd.fused_decode_inputs(params, spec, cfg))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )
