"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret=True),
plus engine-level checks that both execution modes sit on the same kernel
semantics (mode="kernel" lowers onto these kernels; mode="gspmd" onto the
generic jnp operators — results must agree with the numpy oracle)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.filter_count import filter_count
from repro.kernels.flash_attention import flash_mha_fwd
from repro.kernels.merge_join import merge_join_count
from repro.kernels.segment_agg import segment_agg
from repro.kernels.topk_mask import topk_merge

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("n,k,block", [(1000, 1, 256), (5000, 3, 512),
                                       (8192, 2, 4096), (300, 4, 128)])
def test_filter_count_sweep(n, k, block):
    cols = jnp.asarray(RNG.integers(0, 50, (k, n)), jnp.int32)
    bounds = jnp.asarray(np.sort(RNG.integers(0, 50, (k, 2)), axis=1), jnp.int32)
    nv = int(n * 0.9)
    got = filter_count(cols, bounds, nv, block=block)
    want = ref.filter_count(cols, bounds, nv)
    assert int(got) == int(want)


@pytest.mark.parametrize("n,c,g,block", [(1000, 1, 7, 256), (4096, 4, 20, 1024),
                                         (513, 3, 100, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_agg_sweep(n, c, g, block, dtype):
    vals = jnp.asarray(RNG.normal(size=(n, c)), dtype)
    gids = jnp.asarray(RNG.integers(0, g, n), jnp.int32)
    nv = n - 5
    got = segment_agg(vals.astype(jnp.float32), gids, g, nv, block=block)
    want = ref.segment_agg(vals.astype(jnp.float32), gids, g, nv)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


I32 = np.iinfo(np.int32)


def _sorted_random(n, lo, hi):
    return np.sort(RNG.integers(lo, hi, n)).astype(np.int32)


def _sentinel_tail(valid, n):
    return np.concatenate([valid, np.full(n - len(valid), I32.max, np.int32)])


# Each case: (left keys, right keys, nl, nr, block), both key columns sorted
# over their valid prefix (the kernel's contract).
def _random_case(nl, nr, dom, block):
    return lambda: (_sorted_random(nl, 0, dom), _sorted_random(nr, 0, dom),
                    nl - 3, nr - 7, block)


def _wisconsin_case():
    # a sorted permutation (unique1) against a random tenth of it
    n = 20_000
    sub = np.sort(RNG.choice(n, n // 10, replace=False)).astype(np.int32)
    return np.arange(n, dtype=np.int32), sub, n, n // 10, 128


def _equal_case():
    return (np.full(1000, 5, np.int32), np.full(900, 5, np.int32),
            1000, 900, 128)


def _dup_runs_case():
    # runs of 150 and 200 equal keys, so runs cross tile boundaries
    l = np.repeat(np.arange(0, 40, dtype=np.int32), 150)
    r = np.repeat(np.arange(10, 50, 2, dtype=np.int32), 200)
    return l, r, len(l), len(r) - 11, 128


def _empty_left_case():
    return (np.full(300, I32.max, np.int32), _sorted_random(500, 0, 50),
            0, 500, 128)


def _empty_right_case():
    return (_sorted_random(500, 0, 50), np.full(300, I32.max, np.int32),
            500, 0, 128)


def _sentinel_tail_case():
    # valid prefixes end mid-tile, then whole tiles of sentinel; a valid
    # key equal to the sentinel still counts
    lv = np.concatenate([_sorted_random(298, 0, 400), [I32.max] * 2])
    rv = np.concatenate([_sorted_random(447, 0, 400), [I32.max] * 3])
    return (_sentinel_tail(lv.astype(np.int32), 1000),
            _sentinel_tail(rv.astype(np.int32), 900), 300, 450, 128)


def _negative_case():
    l = np.sort(np.concatenate([[I32.min] * 3, RNG.integers(-600, 600, 900)]))
    r = np.sort(np.concatenate([[I32.min] * 2, RNG.integers(-600, 600, 700)]))
    return l.astype(np.int32), r.astype(np.int32), 903, 702, 128


MERGE_JOIN_CASES = {
    "random-500x700": _random_case(500, 700, 50, 128),
    "random-2048x2048": _random_case(2048, 2048, 5000, 512),
    "random-100x4000": _random_case(100, 4000, 10, 256),
    "wisconsin": _wisconsin_case,
    "all-equal": _equal_case,
    "dup-runs": _dup_runs_case,
    "empty-left": _empty_left_case,
    "empty-right": _empty_right_case,
    "sentinel-tail": _sentinel_tail_case,
    "negative": _negative_case,
}


@pytest.mark.parametrize("case,c_max", [
    *[(c, None) for c in MERGE_JOIN_CASES],
    ("all-equal", 3),       # 64 band pairs: a loop of 22 launches
    ("dup-runs", 4),
])
def test_merge_join_sweep(case, c_max, monkeypatch):
    from repro.kernels import merge_join

    if c_max is not None:
        monkeypatch.setattr(merge_join, "C_MAX", c_max)
        merge_join_count.clear_cache()
    l, r, nl, nr, block = MERGE_JOIN_CASES[case]()
    try:
        got = merge_join_count(jnp.asarray(l), jnp.asarray(r), nl, nr,
                               block=block)
    finally:
        if c_max is not None:
            merge_join_count.clear_cache()
    want = ref.merge_join_count(jnp.asarray(l), jnp.asarray(r), nl, nr)
    assert int(got) == int(want)


@pytest.mark.parametrize("case", list(MERGE_JOIN_CASES))
def test_merge_join_band_is_the_overlapping_valid_tiles(case):
    """The band's pairs are exactly the (left, right) tile pairs whose valid
    key ranges meet, by a brute count over every pair."""
    from repro.kernels.merge_join import band

    l, r, nl, nr, block = MERGE_JOIN_CASES[case]()

    def padded(a):
        return _sentinel_tail(a, -(-len(a) // block) * block)

    def ranges(a, n):
        return [(a[s], a[min(s + block, n) - 1]) for s in range(0, n, block)]

    want = {(i, j)
            for i, (llo, lhi) in enumerate(ranges(l, nl))
            for j, (rlo, rhi) in enumerate(ranges(r, nr))
            if llo <= rhi and rlo <= lhi}
    jlo, w, cum = (np.asarray(a) for a in band(
        jnp.asarray(padded(l)), jnp.asarray(padded(r)), nl, nr, block))
    got = {(i, j) for i in range(len(w))
           for j in range(jlo[i], jlo[i] + w[i])}
    assert int(cum[-1]) == len(want)
    assert got == want


@pytest.mark.parametrize("n,k,block", [(2048, 5, 512), (4096, 1, 1024),
                                       (1000, 8, 256)])
def test_topk_sweep(n, k, block):
    sc = jnp.asarray(RNG.normal(size=n), jnp.float32)
    mask = jnp.asarray(RNG.random(n) > 0.2)
    nv = n - 11
    v, i = topk_merge(sc, mask, nv, k, block=block)
    smask = np.where(np.asarray(mask) & (np.arange(n) < nv), np.asarray(sc), -np.inf)
    want = np.sort(smask)[::-1][:k]
    np.testing.assert_allclose(np.asarray(v), want, rtol=1e-6)
    # indices point at the right values
    np.testing.assert_allclose(smask[np.asarray(i)], want, rtol=1e-6)


@pytest.mark.parametrize("B,H,KV,S,D,bq,bk", [
    (1, 2, 2, 128, 16, 32, 32),    # MHA
    (2, 4, 2, 256, 32, 64, 128),   # GQA, uneven blocks
    (1, 8, 1, 64, 64, 64, 16),     # MQA, single q block
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fwd_sweep(B, H, KV, S, D, bq, bk, causal, dtype):
    q = jnp.asarray(RNG.normal(size=(B, H, S, D)), dtype) * 0.3
    k = jnp.asarray(RNG.normal(size=(B, KV, S, D)), dtype) * 0.3
    v = jnp.asarray(RNG.normal(size=(B, KV, S, D)), dtype) * 0.3
    out, lse = flash_mha_fwd(q, k, v, causal=causal, bq=bq, bk=bk)
    want = ref.mha(q, k, v, causal=causal)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_xla_twin_matches_pallas():
    q = jnp.asarray(RNG.normal(size=(2, 4, 128, 32)), jnp.float32) * 0.4
    k = jnp.asarray(RNG.normal(size=(2, 2, 128, 32)), jnp.float32) * 0.4
    v = jnp.asarray(RNG.normal(size=(2, 2, 128, 32)), jnp.float32) * 0.4
    o_pallas, _ = flash_mha_fwd(q, k, v, causal=True, bq=32, bk=32)
    o_xla = ops.flash_attention(q, k, v, True, 32, "xla")
    np.testing.assert_allclose(o_pallas, o_xla, rtol=1e-4, atol=1e-4)


def test_flash_vjp_matches_oracle_grads():
    q = jnp.asarray(RNG.normal(size=(1, 4, 96, 16)), jnp.float32) * 0.4
    k = jnp.asarray(RNG.normal(size=(1, 2, 96, 16)), jnp.float32) * 0.4
    v = jnp.asarray(RNG.normal(size=(1, 2, 96, 16)), jnp.float32) * 0.4
    f = lambda q, k, v: jnp.sum(jnp.tanh(ops.flash_attention(q, k, v, True, 32, "xla")))
    g = lambda q, k, v: jnp.sum(jnp.tanh(ref.mha(q, k, v, causal=True)))
    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_ops_merge_join_backends_agree(backend):
    l = np.sort(RNG.integers(0, 300, 2048)).astype(np.int32)
    r = np.sort(RNG.integers(0, 300, 2048)).astype(np.int32)
    got = ops.merge_join_count(jnp.asarray(l), jnp.asarray(r), 2000, 2010,
                               backend=backend)
    want = ref.merge_join_count(jnp.asarray(l), jnp.asarray(r), 2000, 2010)
    assert int(got) == int(want)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_ops_topk_backends_agree(backend):
    sc = jnp.asarray(RNG.normal(size=4096), jnp.float32)
    mask = jnp.asarray(RNG.random(4096) > 0.3)
    v, i = ops.topk(sc, mask, 4000, 5, backend=backend)
    smask = np.where(np.asarray(mask) & (np.arange(4096) < 4000),
                     np.asarray(sc), -np.inf)
    want = np.sort(smask)[::-1][:5]
    np.testing.assert_allclose(np.asarray(v), want, rtol=1e-6)
    np.testing.assert_allclose(smask[np.asarray(i)], want, rtol=1e-6)


@pytest.mark.parametrize("mode", ["gspmd", "kernel"])
def test_session_mode_matches_numpy(mode):
    """Engine-level sweep: the same queries through either execution mode
    agree with the numpy oracle (the kernel mode rides the ops above)."""
    from repro.core.frame import AFrame
    from repro.data import wisconsin
    from repro.engine.session import Session

    t = wisconsin.generate(3_000, seed=9)
    raw = {k: np.asarray(v) for k, v in t.columns.items()}
    sess = Session(mode=mode)
    sess.create_dataset("data", t, dataverse="m", closed=True)
    df = AFrame("m", "data", session=sess)
    df_r = AFrame("m", "data", session=sess)

    n = len(df[(df["ten"] == 6) & (df["two"] == 0)])
    assert n == int(((raw["ten"] == 6) & (raw["two"] == 0)).sum())
    g = df.groupby("four").agg("count")
    np.testing.assert_array_equal(
        g["count"], [int((raw["four"] == v).sum()) for v in range(4)])
    h = df.sort_values("unique1", ascending=False).head(5)
    np.testing.assert_array_equal(h["unique1"], np.sort(raw["unique1"])[::-1][:5])
    assert len(df.merge(df_r, left_on="unique1", right_on="unique1")) == 3_000


@pytest.mark.parametrize("B,H,KV,S,D,bk", [(2, 4, 2, 256, 32, 64),
                                           (1, 8, 8, 128, 64, 128),
                                           (3, 6, 2, 512, 16, 256)])
def test_flash_decode_sweep(B, H, KV, S, D, bk):
    q = jnp.asarray(RNG.normal(size=(B, H, D)), jnp.float32) * 0.4
    k = jnp.asarray(RNG.normal(size=(B, KV, S, D)), jnp.float32) * 0.4
    v = jnp.asarray(RNG.normal(size=(B, KV, S, D)), jnp.float32) * 0.4
    lens = jnp.asarray(RNG.integers(1, S, B), jnp.int32)
    got = flash_decode(q, k, v, lens, bk=bk)
    want = ref.decode_attention(q, k, v, lens)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
