"""Block-paged KV-cache pool (repro.session.kvpool + the paged serving path).

Host-side: free-list/refcount invariants, chained prefix hashes, LRU
eviction, COW bookkeeping.  Device-side: paged decode attention is
bit-identical to the contiguous layout on both the einsum reference and the
Pallas kernel (bk == page_size), and the paged scheduler reproduces
sequential ``generate()`` token-for-token — including across runs that
share a prefix through the cache, where copy-on-write must keep siblings
independent.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.session import ContinuousBatchingScheduler, InferenceSession
from repro.session.kvpool import (PagedKVManager, PagePool, PrefixCache,
                                  TRASH_PAGE, page_hashes)

_SESS = {}


def _session(arch) -> InferenceSession:
    if arch not in _SESS:
        _SESS[arch] = InferenceSession.from_recipe(arch, reduced=True, seed=0)
    return _SESS[arch]


def _prompts(sess, lens, seed=0, prefix=()):
    rng = np.random.RandomState(seed)
    pre = np.asarray(prefix, np.int32)
    return [np.concatenate([
        pre, rng.randint(1, sess.cfg.vocab_size, size=p).astype(np.int32)])
        for p in lens]


# ---------------------------------------------------------------------------
# host-side bookkeeping
# ---------------------------------------------------------------------------

def test_page_pool_alloc_refcount_release():
    pool = PagePool(5, 4)
    assert pool.n_free == 4 and pool.n_used == 0
    a, b = pool.alloc(2)
    assert TRASH_PAGE not in (a, b) and pool.n_used == 2
    pool.retain([a])
    assert pool.release([a]) == []          # rc 2 -> 1: still allocated
    assert pool.release([a]) == [a]         # rc 1 -> 0: freed
    with pytest.raises(MemoryError, match="need 4"):
        pool.alloc(4)
    with pytest.raises(ValueError):
        pool.release([a])                   # double free
    with pytest.raises(ValueError):
        pool.retain([TRASH_PAGE])           # the trash page is untouchable


def test_page_hashes_are_chained():
    """Equal hash i ⟺ equal FULL prefix through page i, not just page i."""
    a = np.arange(8, dtype=np.int32)
    b = np.arange(8, dtype=np.int32)
    b[0] = 99                               # differs only in page 0
    ha, hb = page_hashes(a, 4), page_hashes(b, 4)
    assert len(ha) == 2
    assert ha[0] != hb[0]
    assert ha[1] != hb[1]                   # page 1 bytes equal, chain differs
    assert ha == page_hashes(a.copy(), 4)


def test_prefix_cache_lookup_register_evict():
    pool = PagePool(8, 4)
    cache = PrefixCache(pool)
    prompt = np.arange(10, dtype=np.int32)  # 2 full pages + tail of 2
    pages = pool.alloc(3)
    cache.register(prompt, pages)
    assert all(pool.refcount(p) == 2 for p in pages)

    # full hit capped at limit: limit=9 walks both full pages, then adopts
    # the partial tail for ONE more token
    got, n = cache.lookup(prompt, limit=9)
    assert n == 9 and got == pages
    assert cache.hits == 1 and cache.hit_tokens == 9
    pool.release(got)

    # a longer prompt sharing the full pages + 2 tail tokens adopts the tail
    longer = np.concatenate([prompt, [77, 78]]).astype(np.int32)
    got, n = cache.lookup(longer, limit=len(longer) - 1)
    assert n == 10 and got == pages
    pool.release(got)

    # divergent page 0 shares nothing
    other = prompt.copy()
    other[0] = 99
    got, n = cache.lookup(other, limit=9)
    assert n == 0 and got == []
    assert cache.hit_rate == pytest.approx(2 / 3)

    # eviction drops the cache's OWN references only: with the registering
    # request still holding its pages nothing frees, after it releases the
    # pool drains fully
    cache.evict(pool.n_pages)
    assert len(cache) == 0 and pool.n_used == 3
    assert pool.release(pages) == pages
    assert pool.n_used == 0


def test_manager_admit_cow_and_free():
    copies = []
    pool = PagePool(9, 4)
    mgr = PagedKVManager(pool, 2, 4, prefix_cache=PrefixCache(pool),
                         copy_page=lambda s, d: copies.append((s, d)))
    p1 = np.arange(10, dtype=np.int32)
    assert mgr.admit(0, p1) == 0            # cold cache: no history
    mgr.register(0, p1)
    row0 = list(mgr.tables[0, :3])

    # sibling shares 2 full pages + adopts the tail -> COWs the boundary page
    p2 = np.concatenate([p1, [70, 71]]).astype(np.int32)
    assert mgr.admit(1, p2) == 10
    assert copies, "boundary page must be copied before the suffix prefill"
    assert mgr.tables[1, 0] == row0[0] and mgr.tables[1, 1] == row0[1]
    assert mgr.tables[1, 2] != row0[2]

    # slot 0's own registered tail page is shared with the cache (rc 2):
    # its first decode write must COW, leaving the published page pristine
    mgr.ensure_writable(0, 10)
    assert mgr.tables[0, 2] != row0[2]
    # growth past the end maps a fresh page; skipping is a bug
    mgr.ensure_writable(0, 12)
    assert mgr.n_mapped[0] == 4
    with pytest.raises(ValueError, match="skips"):
        mgr.ensure_writable(1, 100)

    mgr.free_slot(0)
    mgr.free_slot(1)
    assert (mgr.tables == -1).all()
    mgr.cache.evict(pool.n_pages)
    assert pool.n_used == 0                 # no page leaked


def test_manager_admit_failure_leaks_nothing():
    pool = PagePool(3, 4)                   # 2 allocatable pages
    mgr = PagedKVManager(pool, 1, 4, prefix_cache=PrefixCache(pool))
    with pytest.raises(MemoryError):
        mgr.admit(0, np.arange(12, dtype=np.int32))   # needs 3 pages
    assert pool.n_used == 0 and (mgr.tables == -1).all()
    assert mgr.admit(0, np.arange(8, dtype=np.int32)) == 0


# ---------------------------------------------------------------------------
# kernels: paged gather is bit-identical to the contiguous layout
# ---------------------------------------------------------------------------

def _paged_case(g, ts_list, ps=128, n_max=3, D=16, Hkv=2, seed=0):
    rng = np.random.default_rng(seed)
    B = len(ts_list)
    Hq = Hkv * g
    n_pages = 1 + B * n_max
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((n_pages, ps, Hkv, D)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((n_pages, ps, Hkv, D)), jnp.float32)
    pt = np.full((B, n_max), -1, np.int32)
    page = 1
    for b, t in enumerate(ts_list):
        for i in range((t + ps) // ps):
            pt[b, i] = page
            page += 1
    kc = np.zeros((B, n_max * ps, Hkv, D), np.float32)
    vc = np.zeros((B, n_max * ps, Hkv, D), np.float32)
    pos = np.full((B, n_max * ps), -1, np.int32)
    for b, t in enumerate(ts_list):
        for i in range(n_max):
            if pt[b, i] >= 0:
                kc[b, i * ps:(i + 1) * ps] = np.asarray(k_pool[pt[b, i]])
                vc[b, i * ps:(i + 1) * ps] = np.asarray(v_pool[pt[b, i]])
        pos[b] = np.where(np.arange(n_max * ps) <= t,
                          np.arange(n_max * ps), -1)
    return (q, k_pool, v_pool, jnp.asarray(pt),
            jnp.asarray(np.asarray(ts_list, np.int32)),
            jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(pos))


@pytest.mark.parametrize("g", [1, 2, 4])
def test_paged_reference_matches_contiguous_bitwise(g):
    """Odd lengths, a position ON a page boundary, and a full table — the
    gathered-pool einsum must equal the contiguous einsum bit-for-bit for
    MHA (g=1) and both GQA group counts."""
    from repro.kernels import ref
    ts_list = [5, 128, 255, 383]            # mid-page, boundary, edge, full
    q, kp, vp, pt, ts, kc, vc, pos = _paged_case(g, ts_list)
    out_p = ref.paged_decode_attention_reference(q, kp, vp, pt, ts=ts)
    for b, t in enumerate(ts_list):
        out_c = ref.decode_attention_reference(
            q[b:b + 1], kc[b:b + 1], vc[b:b + 1], pos[b:b + 1],
            t=jnp.int32(t))
        np.testing.assert_array_equal(np.asarray(out_p[b:b + 1]),
                                      np.asarray(out_c))


def _stack(pool, layer, L=3, seed=1):
    """``pool`` at ``layer`` of an L-layer stack whose other layers hold
    other numbers, so a kernel that reads the wrong layer cannot pass."""
    rng = np.random.default_rng(seed)
    stack = rng.standard_normal((L,) + pool.shape).astype(np.float32)
    stack[layer] = np.asarray(pool)
    return jnp.asarray(stack)


@pytest.mark.parametrize("window", [None, 160])
def test_paged_kernel_matches_contiguous_kernel_bitwise(window):
    """The Pallas paged kernel sweeps logical pages with the same online
    softmax as the contiguous kernel: with bk == page_size the two are
    bit-identical (incl. sliding-window masking), read from one layer of
    a stacked pool."""
    from repro.kernels import decode_attention as da
    ts_list = [5, 130, 383]
    q, kp, vp, pt, ts, kc, vc, pos = _paged_case(2, ts_list)
    out_p = da.paged_decode_attention(q, _stack(kp, 1), _stack(vp, 1, seed=2),
                                      pt, ts=ts, layer=jnp.int32(1),
                                      window=window, interpret=True)
    for b, t in enumerate(ts_list):
        out_c = da.decode_attention(
            q[b:b + 1], kc[b:b + 1], vc[b:b + 1], pos[b:b + 1],
            t=jnp.int32(t), window=window, bk=128, interpret=True)
        np.testing.assert_array_equal(np.asarray(out_p[b:b + 1]),
                                      np.asarray(out_c))


def test_paged_kernel_window_matches_reference():
    from repro.kernels import decode_attention as da
    from repro.kernels import ref
    ts_list = [60, 300]
    q, kp, vp, pt, ts, *_ = _paged_case(2, ts_list)
    out_k = da.paged_decode_attention(q, kp[None], vp[None], pt, ts=ts,
                                      layer=jnp.int32(0), window=100,
                                      interpret=True)
    out_r = ref.paged_decode_attention_reference(q, kp, vp, pt, ts=ts,
                                                 window=100)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("layer,window,table,D", [
    (0, None, "live", 16), (2, None, "live", 16), (1, 160, "live", 16),
    (1, None, "dead_tail", 16), (2, 100, "dead_tail", 16),
    (0, None, "holes", 16), (2, 160, "holes", 16),
    (1, None, "dead_tail", 128), (2, 160, "holes", 128)])
def test_paged_kernel_reads_stacked_pool_by_layer(layer, window, table, D):
    """The kernel reads layer ``layer`` of the stacked pool in place and
    equals the reference on that layer's pool: with only live pages
    mapped; with pages mapped past a slot's position (``dead_tail``: the
    index map repeats the last live page and the compute is skipped); and
    with an unmapped page inside the live range (``holes``: masked).  A
    head dim under a lane tile reads pages as (Hkv, D, page_size), a lane
    multiple as (page_size, Hkv, D)."""
    from repro.kernels import decode_attention as da
    from repro.kernels import ref
    ts_list = [5, 130, 200, 383, 0]
    q, kp, vp, pt, ts, *_ = _paged_case(2, ts_list, n_max=4, D=D)
    pt = np.asarray(pt).copy()
    if table == "dead_tail":
        free = iter(range(1 + int(pt.max()), kp.shape[0]))
        for b, t in enumerate(ts_list):
            for i in range(t // 128 + 1, pt.shape[1]):
                pt[b, i] = next(free)
    elif table == "holes":
        pt[1, 0] = -1
        pt[3, 1] = -1
    pt = jnp.asarray(pt)
    k_stack, v_stack = _stack(kp, layer), _stack(vp, layer, seed=2)
    out_k = da.paged_decode_attention(q, k_stack, v_stack, pt, ts=ts,
                                      layer=jnp.int32(layer), window=window,
                                      interpret=True)
    out_r = ref.paged_decode_attention_reference(
        q, k_stack[layer], v_stack[layer], pt, ts=ts, window=window)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-6, rtol=2e-6)


def test_paged_decode_step_kernel_matches_reference_path(monkeypatch):
    """The serving decode step through the Pallas kernel (interpreted here;
    the backend check is forced) emits the same greedy tokens as the
    reference path on a dense GQA config."""
    from repro.kernels import ops
    prompts = _prompts(_session("granite_3_2b"), (5, 19, 9))
    budgets = [12, 6, 9]

    def serve():
        sess = InferenceSession.from_recipe("granite_3_2b", reduced=True,
                                            seed=0)
        return sess.serve(prompts, budgets, n_slots=2, paged=True,
                          page_size=8)[0]

    want = serve()
    monkeypatch.setattr(ops, "paged_kernel_fits", lambda page_size: True)
    got = serve()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# serving: the paged scheduler reproduces generate() exactly
# ---------------------------------------------------------------------------

def test_paged_scheduler_matches_generate_gqa():
    """Mixed prompt lengths crossing page boundaries through the paged pool
    == each request decoded alone (granite reduced is GQA: 4 q-heads over
    2 kv-heads), and the paged stats fields are populated."""
    sess = _session("granite_3_2b")
    prompts = _prompts(sess, (5, 9, 17, 3))
    budgets = [10, 3, 6, 8]
    outs, stats = sess.serve(prompts, budgets, n_slots=2, paged=True,
                             page_size=8)
    for p, m, o in zip(prompts, budgets, outs):
        ref = np.asarray(sess.generate(jnp.asarray(p)[None], m)[0])
        np.testing.assert_array_equal(o, ref)
    assert stats.requests == 4
    assert stats.generated_tokens == sum(budgets)
    assert stats.page_size == 8 and stats.pool_pages > 0
    assert 0.0 < stats.pool_occupancy <= 1.0
    assert stats.prompt_tokens == sum(len(p) for p in prompts)
    assert stats.prefill_tokens <= stats.prompt_tokens


def test_paged_scheduler_matches_generate_mha():
    """Same contract on a pure-MHA head layout (n_kv_heads == n_heads)."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("granite_3_2b").reduced(),
                              n_kv_heads=4)
    sess = InferenceSession.from_recipe(cfg, seed=0)
    prompts = _prompts(sess, (6, 11))
    outs, _ = sess.serve(prompts, [5, 4], n_slots=2, paged=True, page_size=8)
    for p, m, o in zip(prompts, [5, 4], outs):
        ref = np.asarray(sess.generate(jnp.asarray(p)[None], m)[0])
        np.testing.assert_array_equal(o, ref)


def test_prefix_cache_shares_across_runs():
    """Two serve() waves through ONE scheduler: the second wave's prompts
    open with the same system prompt, so admission maps the cached pages
    and prefills only the suffix — outputs stay exact."""
    sess = _session("granite_3_2b")
    from repro.session import RequestQueue
    sysp = _prompts(sess, (16,), seed=7)[0]
    sched = ContinuousBatchingScheduler(sess, n_slots=2, max_len=48,
                                        paged=True, page_size=8)

    def wave(lens, budgets, seed):
        prompts = _prompts(sess, lens, seed=seed, prefix=sysp)
        queue = RequestQueue()
        rids = [queue.submit(p, m) for p, m in zip(prompts, budgets)]
        outputs, stats = sched.run(queue)
        for rid, p, m in zip(rids, prompts, budgets):
            ref = np.asarray(sess.generate(jnp.asarray(p)[None], m)[0])
            np.testing.assert_array_equal(outputs[rid], ref)
        return stats

    s1 = wave((4, 6), [4, 5], seed=1)
    s2 = wave((5, 3), [3, 6], seed=2)
    assert s2.prefix_hits == 2                  # both admissions shared sysp
    assert s2.prefix_hit_rate > 0.5
    assert s2.prefill_tokens < s2.prompt_tokens
    assert s1.prefill_tokens + s2.prefill_tokens < \
        s1.prompt_tokens + s2.prompt_tokens


def test_cow_sibling_isolation():
    """Two requests adopting the same cached prefix then diverging: each
    slot's writes land in privately-owned (copied) pages, so neither
    perturbs the other or the published prefix — every output matches its
    solo decode exactly, across a third wave re-reading the prefix."""
    sess = _session("granite_3_2b")
    from repro.session import RequestQueue
    sysp = _prompts(sess, (12,), seed=9)[0]
    sched = ContinuousBatchingScheduler(sess, n_slots=2, max_len=40,
                                        paged=True, page_size=8)
    waves = [
        _prompts(sess, (4,), seed=3, prefix=sysp),          # publishes sysp
        _prompts(sess, (3, 7), seed=4, prefix=sysp),        # siblings diverge
        _prompts(sess, (5,), seed=5, prefix=sysp),          # prefix intact?
    ]
    for prompts in waves:
        queue = RequestQueue()
        budgets = [6] * len(prompts)
        rids = [queue.submit(p, m) for p, m in zip(prompts, budgets)]
        outputs, _ = sched.run(queue)
        for rid, p, m in zip(rids, prompts, budgets):
            ref = np.asarray(sess.generate(jnp.asarray(p)[None], m)[0])
            np.testing.assert_array_equal(outputs[rid], ref)


def test_pool_pressure_defers_admission():
    """A pool too small for every request at once still drains the queue
    correctly: admissions the free list can't hold are deferred (FIFO
    preserved) and retry after a retire frees pages."""
    sess = _session("granite_3_2b")
    prompts = _prompts(sess, (8, 8, 8))
    budgets = [6, 6, 6]
    # each request needs ceil(14/8)=2 pages; 5 allocatable pages < 3*2, so
    # the third admission must wait for a retire (prefix sharing off keeps
    # the arithmetic exact)
    outs, stats = sess.serve(prompts, budgets, n_slots=3, paged=True,
                             page_size=8, n_pages=6, prefix_sharing=False)
    for p, m, o in zip(prompts, budgets, outs):
        ref = np.asarray(sess.generate(jnp.asarray(p)[None], m)[0])
        np.testing.assert_array_equal(o, ref)
    assert stats.requests == 3


def test_paged_rejects_impossible_and_recurrent():
    """Preflight rejects a request that can't fit the pool even when idle;
    recurrent families can't construct a paged scheduler at all."""
    sess = _session("granite_3_2b")
    from repro.session import RequestQueue
    sched = ContinuousBatchingScheduler(sess, n_slots=1, max_len=32,
                                        paged=True, page_size=8, n_pages=3)
    queue = RequestQueue()
    queue.submit(np.zeros(20, np.int32), 10)    # needs 4 pages, pool has 2
    with pytest.raises(ValueError, match="pages"):
        sched.run(queue)
    assert len(queue) == 1                      # nothing popped

    ssm = _session("xlstm_125m")
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatchingScheduler(ssm, n_slots=1, max_len=16, paged=True)
