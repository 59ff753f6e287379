"""Paged KV pool: lifecycle, block tables, fork alignment, KV round-trip,
in-place writes of tokens and prompts."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kv_pool import KVPoolConfig, PagedKVPool


def mk(policy="puma", dtype="float32", **kw):
    cfg = KVPoolConfig(
        num_blocks=64, block_size=4, kv_heads=2, head_dim=8, n_layers=2,
        max_seqs=8, max_blocks_per_seq=16, blocks_per_arena=16,
        policy=policy, dtype=dtype, **kw,
    )
    return PagedKVPool(cfg)


def test_admit_release_cycle():
    p = mk()
    slots = [p.admit(10) for _ in range(4)]
    assert all(s is not None for s in slots)
    tbl = p.block_table()
    for s in slots:
        assert (tbl[s] >= 0).sum() == 3  # ceil(10/4)
    for s in slots:
        p.release(s)
    assert p.pool.free_tiles() == p.pool.total_tiles


def test_append_token_extends_blocks():
    p = mk()
    s = p.admit(4)          # exactly one block
    assert (p.block_table()[s] >= 0).sum() == 1
    p.append_token(s)       # 5th token -> new block
    assert (p.block_table()[s] >= 0).sum() == 2
    assert p.seq_lens()[s] == 5


def test_fork_mirrors_parent_arenas():
    p = mk()
    s = p.admit(20)  # 5 blocks: parent + fork both fit one 16-block arena
    f = p.fork(s)
    tbl = p.block_table()
    arena = lambda b: b // p.cfg.blocks_per_arena
    pb = tbl[s][tbl[s] >= 0]
    fb = tbl[f][tbl[f] >= 0]
    assert len(pb) == len(fb)
    assert [arena(b) for b in pb] == [arena(b) for b in fb]


def test_kv_roundtrip():
    p = mk()
    s = p.admit(10)
    k = jnp.arange(2 * 10 * 2 * 8, dtype=jnp.float32).reshape(2, 10, 2, 8)
    v = -k
    p.write_prompt_kv(s, k, v)
    tbl = p.block_table()[s]
    blocks = tbl[tbl >= 0]
    got_k = np.asarray(p.k[:, blocks]).reshape(2, -1, 2, 8)[:, :10]
    np.testing.assert_allclose(got_k, np.asarray(k))
    # single-token write at position 10
    p.append_token(s)
    k1 = jnp.full((2, 1, 2, 8), 7.0)
    p.write_token_kv([p.token_dest(s)], k1, -k1)
    tbl = p.block_table()[s]
    blocks = tbl[tbl >= 0]
    got = np.asarray(p.k[:, blocks]).reshape(2, -1, 2, 8)[:, 10]
    np.testing.assert_allclose(got, 7.0)
    got = np.asarray(p.v[:, blocks]).reshape(2, -1, 2, 8)[:, 10]
    np.testing.assert_allclose(got, -7.0)


def _fill(p, seed):
    """Give every page of both pools distinct non-zero contents."""
    rng = np.random.default_rng(seed)
    p.k = jnp.asarray(rng.normal(size=p.k.shape) + 3.0, p.k.dtype)
    p.v = jnp.asarray(rng.normal(size=p.v.shape) - 3.0, p.v.dtype)
    return np.array(p.k), np.array(p.v)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lens", [[4], [7, 4, 1], [10, 8, 3, 16, 5]])
def test_token_write_lands_every_layer_and_sequence(lens, dtype):
    """One call writes B sequences x L layers to each token's page and
    offset (a length that is a whole number of pages opens a new page on
    its next token); every other element of the pools is unchanged."""
    p = mk(dtype=dtype)
    slots = [p.admit(n) for n in lens]
    for s in slots:
        p.append_token(s)
    ref_k, ref_v = _fill(p, len(lens))
    rng = np.random.default_rng(7)
    L, B, KV, hd = 2, len(lens), 2, 8
    k = rng.normal(size=(L, B, KV, hd)).astype(np.float32)
    v = rng.normal(size=(L, B, KV, hd)).astype(np.float32)
    dests = [p.token_dest(s) for s in slots]
    for (block, off), s, n in zip(dests, slots, lens):
        assert block == p.tiles_of(s)[n // 4] and off == n % 4
    assert any(off == 0 for _, off in dests)  # a token opens a new page
    p.write_token_kv(dests, jnp.asarray(k), jnp.asarray(v))
    for i, (block, off) in enumerate(dests):
        ref_k[:, block, off] = k[:, i].astype(ref_k.dtype)
        ref_v[:, block, off] = v[:, i].astype(ref_v.dtype)
    np.testing.assert_array_equal(np.asarray(p.k), ref_k)
    np.testing.assert_array_equal(np.asarray(p.v), ref_v)


@pytest.mark.parametrize("n_tokens", [1, 10, 13])
def test_prompt_write_pads_the_last_page_with_zeros(n_tokens):
    p = mk()
    other = p.admit(6)
    s = p.admit(n_tokens)
    ref_k, ref_v = _fill(p, n_tokens)
    rng = np.random.default_rng(n_tokens)
    k = rng.normal(size=(2, n_tokens, 2, 8)).astype(np.float32)
    v = rng.normal(size=(2, n_tokens, 2, 8)).astype(np.float32)
    p.write_prompt_kv(s, jnp.asarray(k), jnp.asarray(v))
    pages = p.tiles_of(s)
    assert len(pages) * 4 > n_tokens          # the last page has a tail
    pad = ((0, 0), (0, len(pages) * 4 - n_tokens), (0, 0), (0, 0))
    ref_k[:, pages] = np.pad(k, pad).reshape(2, len(pages), 4, 2, 8)
    ref_v[:, pages] = np.pad(v, pad).reshape(2, len(pages), 4, 2, 8)
    got_k, got_v = np.asarray(p.k), np.asarray(p.v)
    np.testing.assert_array_equal(got_k, ref_k)
    np.testing.assert_array_equal(got_v, ref_v)
    assert not got_k[:, pages].reshape(2, -1, 2, 8)[:, n_tokens:].any()
    assert got_k[:, p.tiles_of(other)].all()    # a neighbour's pages kept


@pytest.mark.parametrize("pages", [
    list(range(20, 30)),                                  # one run
    [40, 41, 42, 5, 6, 60, 61, 62, 63, 20],               # runs out of order, one at the end
    list(range(63, 43, -2)),                              # no two pages adjacent
])
def test_prompt_write_follows_any_placement(pages):
    """The prompt write goes run by run through an n-page window; a run
    too near the pool's end for a window starting at it, runs out of
    order and single pages all land where the page list says."""
    p = mk()
    s = p.admit(len(pages) * 4 - 3)
    p._seqs[s][0].tiles[:] = pages        # placement chosen by the test
    ref_k, ref_v = _fill(p, 1)
    rng = np.random.default_rng(2)
    k = rng.normal(size=(2, 1, len(pages) * 4 - 3, 2, 8)).astype(np.float32)
    p.write_prompt_kv(s, jnp.asarray(k), jnp.asarray(-k))
    x = np.pad(k[:, 0], ((0, 0), (0, 3), (0, 0), (0, 0))).reshape(2, len(pages), 4, 2, 8)
    ref_k[:, pages] = x
    ref_v[:, pages] = -x
    np.testing.assert_array_equal(np.asarray(p.k), ref_k)
    np.testing.assert_array_equal(np.asarray(p.v), ref_v)


@pytest.mark.parametrize("write", ["token", "prompt"])
def test_write_is_in_place(write):
    """The writes donate the pools: the previous buffers are gone, so no
    copy of a pool outlives a write."""
    p = mk()
    s = p.admit(10)
    old_k, old_v = p.k, p.v
    if write == "token":
        one = jnp.ones((2, 1, 2, 8))
        p.write_token_kv([p.token_dest(s)], one, one)
    else:
        ten = jnp.ones((2, 10, 2, 8))
        p.write_prompt_kv(s, ten, ten)
    assert old_k.is_deleted() and old_v.is_deleted()
    assert not p.k.is_deleted() and not p.v.is_deleted()
    assert p.k.shape == old_k.shape and p.k.dtype == old_k.dtype


def test_pool_exhaustion_rejects_admit():
    p = mk()
    got = [p.admit(64 * 4 // 2) for _ in range(3)]  # each takes half the pool
    assert got[0] is not None and got[1] is not None and got[2] is None
