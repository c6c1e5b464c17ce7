"""Paged decode-attention: Pallas kernel (interpret) vs pure-JAX ref vs a
direct dense computation, across GQA ratios, page sizes, ragged lengths, and
a fragmented page table."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import kernel, ops, ref
from repro.models.attention import naive_attention


def _paged_case(seed, b, hq, hkv, d, page_size, num_pages, max_pages,
                seq_lens, dtype=jnp.float32, layers=()):
    """Random q + pools; page table fragmented (shuffled physical ids).
    ``layers=(L,)`` makes the pools a stack of L layers, each its own."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, hq, d)), dtype)
    pool = tuple(layers) + (num_pages, page_size, hkv, d)
    k_pages = jnp.asarray(rng.normal(size=pool), dtype)
    v_pages = jnp.asarray(rng.normal(size=pool), dtype)
    ids = rng.permutation(np.arange(1, num_pages))[:b * max_pages]
    page_table = jnp.asarray(ids.reshape(b, max_pages).astype(np.int32))
    return q, k_pages, v_pages, page_table, jnp.asarray(seq_lens, jnp.int32)


def _block_edges(page_size, hkv, d, max_pages, dtype):
    """Live lengths at every edge of the decode walk's blocks (one token,
    a page less one, a page, a block, a block and one, the full context),
    with empty slots among them."""
    block = page_size * ops.pages_per_block(
        page_size, hkv * d * jnp.dtype(dtype).itemsize, max_pages)
    return [1, 0, page_size - 1, page_size, block, 0, block + 1,
            max_pages * page_size]


def _small(page_size, hq, hkv):
    return pytest.param(page_size, hq, hkv, 16, 4, 16, jnp.float32,
                        [1, page_size * 2 + 3, page_size * 4], False,
                        id=f"{page_size}-{hq}-{hkv}")


def _edges(hq, hkv, dtype, shared=False):
    name = f"edges-hkv{hkv}-{jnp.dtype(dtype).name}" + \
        ("-shared" if shared else "")
    return pytest.param(16, hq, hkv, 128, 40, 8 * 40 + 1, dtype,
                        _block_edges(16, hkv, 128, 40, dtype), shared,
                        id=name)


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one-pool", "stack"])
@pytest.mark.parametrize(
    "page_size,hq,hkv,d,max_pages,num_pages,dtype,seq_lens,shared",
    [_small(4, 4, 1), _small(8, 4, 2), _small(16, 4, 4), _small(8, 6, 2),
     _edges(4, 1, jnp.float32), _edges(4, 2, jnp.float32),
     _edges(16, 8, jnp.float32), _edges(16, 8, jnp.bfloat16),
     _edges(16, 8, jnp.bfloat16, shared=True)])
def test_kernel_matches_ref(page_size, hq, hkv, d, max_pages, num_pages,
                            dtype, seq_lens, shared, stacked):
    """Live slots match the ref and empty ones are zero, on fragmented page
    tables (shuffled physical ids); ``shared`` has the last slot reuse the
    scattered pages of the one before it (a cached prefix) for half its
    table. ``stacked`` hands the kernel the case's pools as layer 1 of a
    two-layer stack whose layer 0 holds other values."""
    q, kp, vp, pt, sl = _paged_case(0, len(seq_lens), hq, hkv, d, page_size,
                                    num_pages, max_pages, seq_lens, dtype)
    if shared:
        pt = pt.at[-1, :max_pages // 2].set(pt[-2, :max_pages // 2])
    o_ref = np.asarray(ref.paged_decode_attention(q, kp, vp, pt, sl),
                       np.float32)
    pools, layer = (kp, vp), 0
    if stacked:
        pools, layer = (jnp.stack([-vp, kp]), jnp.stack([kp, vp])), 1
    o_k = np.asarray(kernel.paged_decode_attention_fwd(
        q, *pools, pt, sl, layer, interpret=True), np.float32)
    live = np.asarray(seq_lens) > 0
    atol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(o_k[live], o_ref[live], atol=atol)
    assert not o_k[~live].any()


@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4)], ids=["gqa", "mha"])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_stacked_kernel_reads_its_layer(hq, hkv, layer):
    """A three-layer stack whose layers hold different values: the kernel
    (with the layer index traced, as the model step passes it) and the ref
    both read the pool at ``layer`` and no other. Slots 1 and 3 are
    inactive with null-page rows, and live rows past their pages hold the
    null page too."""
    lens = [9, 0, 16, 0, 27]
    page, max_pages = 8, 4
    q, kp, vp, pt, sl = _paged_case(4, len(lens), hq, hkv, 16, page, 24,
                                    max_pages, lens, layers=(3,))
    live_pages = -(-sl // page)
    pt = jnp.where(jnp.arange(max_pages)[None] < live_pages[:, None], pt, 0)
    want = [np.asarray(ref.paged_decode_attention(q, kp[i], vp[i], pt, sl))
            for i in range(3)]
    got = np.asarray(jax.jit(functools.partial(
        kernel.paged_decode_attention_fwd, interpret=True))(
            q, kp, vp, pt, sl, jnp.int32(layer)))
    np.testing.assert_array_equal(
        np.asarray(ref.paged_decode_attention(q, kp, vp, pt, sl, layer)),
        want[layer])
    live = np.asarray(sl) > 0
    np.testing.assert_allclose(got[live], want[layer][live], atol=1e-5)
    assert not got[~live].any()
    for other in {0, 1, 2} - {layer}:
        assert np.abs(got[live] - want[other][live]).max() > 0.1, other


@pytest.mark.parametrize("hkv,dtype", [(1, jnp.float32), (2, jnp.float32),
                                       (8, jnp.float32), (8, jnp.bfloat16)])
def test_kv_blocks_counts_the_kernels_walk(hkv, dtype):
    """The kernel's K and V buffers hold the block the shared rule gives,
    and ``ops.kv_blocks`` counts the blocks its walk takes: one for every
    started block of each sequence, none for an empty one."""
    page, d, max_pages = 16, 128, 40
    lens = _block_edges(page, hkv, d, max_pages, dtype)
    case = _paged_case(0, len(lens), 2 * hkv, hkv, d, page,
                       len(lens) * max_pages + 1, max_pages, lens, dtype)
    jaxpr = jax.make_jaxpr(functools.partial(
        kernel.paged_decode_attention_fwd, interpret=True))(*case).jaxpr
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    # K and V: [2 buffers, block tokens x Hkv rows, D]
    bufs = [v.aval.shape for v in call.params["jaxpr"].invars
            if len(v.aval.shape) == 3 and v.aval.shape[0] == 2]
    assert len(bufs) == 2 and bufs[0] == bufs[1], bufs
    block = bufs[0][1] // hkv
    assert block == page * ops.pages_per_block(
        page, hkv * d * jnp.dtype(dtype).itemsize, max_pages)
    assert block < max_pages * page
    assert ops.kv_blocks(lens, block) == sum(math.ceil(n / block)
                                             for n in lens)
    assert ops.kv_blocks([0, 1, block, block + 1], block) == 4


def test_kernel_zeroes_inactive_slots():
    case = _paged_case(1, 4, 4, 2, 8, 8, 12, 2, seq_lens=[5, 0, 9, 0])
    o_k = kernel.paged_decode_attention_fwd(*case, interpret=True)
    assert float(jnp.max(jnp.abs(o_k[1]))) == 0.0
    assert float(jnp.max(jnp.abs(o_k[3]))) == 0.0
    assert float(jnp.max(jnp.abs(o_k[0]))) > 0.0


def test_ref_matches_dense_gather():
    """The paged ref == dense attention over the same logical K/V rows."""
    b, hq, hkv, d, page, maxp = 2, 4, 2, 16, 4, 3
    q, kp, vp, pt, sl = _paged_case(2, b, hq, hkv, d, page, 16, maxp,
                                    seq_lens=[7, 11])
    o_paged = ref.paged_decode_attention(q, kp, vp, pt, sl)
    # densify: walk the page table row by row
    k = np.zeros((b, maxp * page, hkv, d), np.float32)
    v = np.zeros_like(k)
    for i in range(b):
        for j in range(maxp):
            k[i, j * page:(j + 1) * page] = np.asarray(kp)[int(pt[i, j])]
            v[i, j * page:(j + 1) * page] = np.asarray(vp)[int(pt[i, j])]
    o_dense = naive_attention(q[:, None], jnp.asarray(k), jnp.asarray(v),
                              causal=False, kv_len=sl)[:, 0]
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_dense),
                               atol=1e-6)


def test_kernel_fragmented_vs_contiguous_equivalence():
    """Physical placement must not matter: the same logical K/V served from a
    contiguous table and from a scattered one give identical outputs."""
    b, hq, hkv, d, page, maxp, P = 2, 4, 2, 8, 4, 3, 16
    rng = np.random.default_rng(5)
    rows_k = rng.normal(size=(b, maxp * page, hkv, d)).astype(np.float32)
    rows_v = rng.normal(size=(b, maxp * page, hkv, d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    sl = jnp.asarray([9, 12], jnp.int32)

    def build(assignment):
        kp = np.zeros((P, page, hkv, d), np.float32)
        vp = np.zeros_like(kp)
        pt = np.zeros((b, maxp), np.int32)
        for i in range(b):
            for j in range(maxp):
                pid = assignment[i][j]
                kp[pid] = rows_k[i, j * page:(j + 1) * page]
                vp[pid] = rows_v[i, j * page:(j + 1) * page]
                pt[i, j] = pid
        return (jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt))

    contiguous = build([[1, 2, 3], [4, 5, 6]])
    fragmented = build([[11, 3, 7], [14, 1, 9]])
    o1 = kernel.paged_decode_attention_fwd(q, *contiguous, sl, interpret=True)
    o2 = kernel.paged_decode_attention_fwd(q, *fragmented, sl, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=0)


# --------------------------------------------------------------- paged prefill ---

def _prefill_case(seed, hq, hkv, d, page_size, num_pages, max_pages, chunk,
                  dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(chunk, hq, d)), dtype)
    k_pages = jnp.asarray(rng.normal(size=(num_pages, page_size, hkv, d)),
                          dtype)
    v_pages = jnp.asarray(rng.normal(size=(num_pages, page_size, hkv, d)),
                          dtype)
    row = rng.permutation(np.arange(1, num_pages))[:max_pages]
    return q, k_pages, v_pages, jnp.asarray(row.astype(np.int32))


@pytest.mark.parametrize("page_size,hq,hkv,start,valid",
                         [(4, 4, 2, 0, 8),     # aligned, full chunk
                          (4, 4, 1, 4, 5),     # one cached page behind
                          (8, 6, 2, 3, 4),     # unaligned start (CoW tail)
                          (4, 4, 4, 8, 2)])    # mostly-padded chunk
def test_prefill_kernel_matches_ref(page_size, hq, hkv, start, valid):
    """Chunked-prefill kernel == gather ref on every valid row, for aligned
    and mid-page (post-CoW) chunk starts."""
    chunk, maxp = 8, 5
    q, kp, vp, row = _prefill_case(0, hq, hkv, 16, page_size, 24, maxp, chunk)
    total = start + valid
    o_ref = ref.paged_prefill_attention(q, kp, vp, row, start, total)
    o_k = kernel.paged_prefill_attention_fwd(q, kp, vp, row, start, total,
                                             interpret=True)
    np.testing.assert_allclose(np.asarray(o_k)[:valid],
                               np.asarray(o_ref)[:valid], atol=1e-5)


def test_prefill_ref_matches_dense_gather():
    """Causal chunk rows == dense attention over the same logical K/V with
    the chunk offset folded into the causal mask."""
    chunk, page, maxp, hq, hkv, d, start, valid = 6, 4, 4, 4, 2, 8, 4, 6
    q, kp, vp, row = _prefill_case(1, hq, hkv, d, page, 16, maxp, chunk)
    total = start + valid
    o_paged = ref.paged_prefill_attention(q, kp, vp, row, start, total)
    k = np.asarray(kp)[np.asarray(row)].reshape(1, -1, hkv, d)
    v = np.asarray(vp)[np.asarray(row)].reshape(1, -1, hkv, d)
    o_dense = naive_attention(q[None], jnp.asarray(k), jnp.asarray(v),
                              causal=True, q_offset=start,
                              kv_len=jnp.asarray([total], jnp.int32))[0]
    np.testing.assert_allclose(np.asarray(o_paged)[:valid],
                               np.asarray(o_dense)[:valid], atol=1e-6)


def test_prefill_kernel_first_chunk_sees_only_itself():
    """start == 0: row i attends to rows <= i regardless of stale page
    content past the chunk (kv_len masking)."""
    chunk, page, maxp, hq, hkv, d = 4, 4, 3, 4, 2, 8
    q, kp, vp, row = _prefill_case(2, hq, hkv, d, page, 12, maxp, chunk)
    o_k = kernel.paged_prefill_attention_fwd(q, kp, vp, row, 0, chunk,
                                             interpret=True)
    # row 0 can see exactly one K/V row -> output == that row's v (per head)
    v0 = np.asarray(vp)[int(row[0]), 0]                     # [hkv, d]
    expect = np.repeat(v0, hq // hkv, axis=0)               # GQA broadcast
    np.testing.assert_allclose(np.asarray(o_k)[0], expect, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    case = _paged_case(3, 2, 4, 2, 16, 8, 12, 3, seq_lens=[6, 20],
                      dtype=dtype)
    o_ref = ref.paged_decode_attention(*case)
    o_k = kernel.paged_decode_attention_fwd(*case, interpret=True)
    atol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_ref, np.float32), atol=atol)
