"""The paged decode attention kernel (kernels/paged_decode_attention.py) in
interpret mode against its gathering oracle (kernels/ref.py, the gather
path of decode_attention) on the same pool: shuffled block tables,
positions at the edges of pages and of the table, free slots on the null
page, a first valid position, grouped heads, head widths 64 and 128,
pages of 8 and 16, and bf16 pools.

Tolerances. With float32 queries the kernel and the gather path compute
the same float32 products; only the order of the sums differs (an online
softmax over blocks, its partial sums rescaled as the running max moves,
against one softmax over the row), so they agree to a few float32 ulps of
the output (atol = rtol = 1e-5 covers sums of a few hundred terms). With
bf16 queries both paths round one float32 value to bf16 at the end, so
they may differ by the one bf16 ulp that such a rounding can flip (a
bf16 ulp is at most 2**-7 of the value: 7 stored mantissa bits).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import paged_decode_attention as PDA
from repro.kernels import ops, ref
from repro.models import attention as A

F32_TOL = dict(atol=1e-5, rtol=1e-5)


def _pool(rng, P, page, Hkv, hd, dtype):
    return jnp.asarray(rng.standard_normal((P, page, Hkv, hd)), dtype)


def _rows(rng, page, nb, dead=(4,)):
    """Positions at 0, page-1, page and the table's last position, one
    random, and free slots (all-null tables, stale positions); block tables
    drawn from a shuffled pool, each row mapping its pages up to its
    position and the null page past it."""
    pos = np.array([0, page - 1, page, nb * page - 1,
                    int(rng.integers(1, nb * page)), 3 * page + 1],
                   np.int32)
    P = int(sum(p // page + 1 for p in pos)) + 1
    perm = rng.permutation(np.arange(1, P))
    tbl = np.zeros((len(pos), nb), np.int32)
    k = 0
    for b, p in enumerate(pos):
        n = p // page + 1
        tbl[b, :n] = perm[k:k + n]
        k += n
    for b in dead:
        tbl[b] = 0
    return jnp.asarray(pos), jnp.asarray(tbl), P, np.array(
        [b not in dead for b in range(len(pos))])


def _case(seed, Hkv, rep, hd, page, nb, pool_dtype, q_dtype):
    rng = np.random.default_rng(seed)
    pos, tbl, P, live = _rows(rng, page, nb)
    kp = _pool(rng, P, page, Hkv, hd, pool_dtype)
    vp = _pool(rng, P, page, Hkv, hd, pool_dtype)
    q = jnp.asarray(rng.standard_normal((len(live), 1, Hkv * rep, hd)),
                    q_dtype)
    return q, kp, vp, pos, tbl, live


def _check(q, kp, vp, pos, tbl, live, kv_start=None, **kw):
    got = ops.paged_decode_attention(q, kp, vp, pos, tbl, kv_start,
                                     interpret=True, **kw)
    want = ref.paged_decode_attention_ref(q, kp, vp, pos, tbl, kv_start)
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if q.dtype == jnp.float32:
        np.testing.assert_allclose(got[live], want[live], **F32_TOL)
    else:
        np.testing.assert_allclose(got[live], want[live], rtol=2.0 ** -7,
                                   atol=0)
    # a free slot reads nothing: finite zeros, whatever its stale position
    assert np.all(got[~live] == 0)
    return got


@pytest.mark.parametrize("rep,hd,page", [(1, 64, 8), (3, 64, 16),
                                         (4, 128, 16), (3, 128, 8),
                                         (4, 64, 16), (1, 128, 8)])
def test_kernel_matches_gather_path(rep, hd, page):
    _check(*_case(rep * 100 + hd + page, 2, rep, hd, page, 6,
                  jnp.float32, jnp.float32))


@pytest.mark.parametrize("q_dtype", [jnp.float32, jnp.bfloat16])
def test_bf16_pool(q_dtype):
    _check(*_case(7, 2, 3, 64, 16, 6, jnp.bfloat16, q_dtype))


def test_kv_start_excludes_leading_positions():
    q, kp, vp, pos, tbl, live = _case(11, 2, 3, 64, 8, 6, jnp.float32,
                                      jnp.float32)
    # first valid positions inside the first page, on a page boundary, past
    # a whole page, and at the row's own position
    start = jnp.minimum(jnp.array([0, 3, 8, 13, 21, 20]), pos)
    got = _check(q, kp, vp, pos, tbl, live, start)
    full = np.asarray(ops.paged_decode_attention(q, kp, vp, pos, tbl,
                                                 interpret=True))
    moved = live & (np.asarray(start) > 0)
    assert moved.any() and not np.allclose(got[moved], full[moved])


@pytest.mark.parametrize("ppb", [1, 4, 6])
def test_pages_per_block_does_not_change_the_result(ppb):
    """One page a block, blocks that leave a partial last block (4 of a
    6-entry table), and the whole table in one block."""
    _check(*_case(13, 2, 3, 64, 8, 6, jnp.float32, jnp.float32),
           pages_per_block=ppb)


def test_page_ranges_read_only_live_pages():
    """The pages a row reads: pos // page + 1 from its first valid page;
    none for a row whose table maps its position to the null page."""
    page = 16
    pos = jnp.array([0, 15, 16, 4095, 700, 4000], jnp.int32)
    start = jnp.array([0, 0, 0, 40, 0, 0], jnp.int32)
    tbl = jnp.asarray(np.where(np.arange(256)[None, :] <=
                               (np.asarray(pos) // page)[:, None], 7, 0)
                      * np.array([1, 1, 1, 1, 1, 0])[:, None], jnp.int32)
    lo, hi = PDA.page_ranges(pos, start, tbl, page)
    assert np.asarray(lo).tolist() == [0, 0, 0, 2, 0, 0]
    assert np.asarray(hi).tolist() == [1, 1, 2, 256, 44, 0]


def test_decode_attention_keeps_the_gather_path_off_the_tpu():
    """On the CPU decode_attention's paged call is the gather path, the
    bit-exact one the paged-vs-contiguous parity tests hold."""
    q, kp, vp, pos, tbl, _ = _case(17, 2, 3, 64, 8, 6, jnp.float32,
                                   jnp.float32)
    assert jax.default_backend() == "cpu"
    np.testing.assert_array_equal(
        np.asarray(A.decode_attention(q, kp, vp, pos, block_table=tbl)),
        np.asarray(ref.paged_decode_attention_ref(q, kp, vp, pos, tbl)))
