"""Continuous-batching serving path: scheduler output is token-for-token
identical to sequential per-request greedy decode (dense KV rings AND
non-KV recurrent state caches), prefill-based prompt ingestion matches the
old token-by-token replay, and slots are reused mid-flight."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.session import (ContinuousBatchingScheduler, InferenceSession,
                           RequestQueue)

_SESS = {}


def _session(arch) -> InferenceSession:
    if arch not in _SESS:
        _SESS[arch] = InferenceSession.from_recipe(arch, reduced=True, seed=0)
    return _SESS[arch]


def _prompts(sess, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, sess.cfg.vocab_size, size=p).astype(np.int32)
            for p in lens]


@pytest.mark.parametrize("arch", ["granite_3_2b",   # dense: ring-buffer KV
                                  "xlstm_125m"])    # ssm: mLSTM/sLSTM states
def test_scheduler_matches_sequential_decode(arch):
    """Mixed prompt lengths + budgets through 2 slots == each request decoded
    alone through ``generate()`` — slot insert/reset must be exact across the
    family's cache layout."""
    sess = _session(arch)
    prompts = _prompts(sess, (5, 9, 5, 12))
    budgets = [10, 3, 6, 4]
    outs, stats = sess.serve(prompts, budgets, n_slots=2)
    assert stats.requests == 4
    assert stats.generated_tokens == sum(budgets)
    for p, m, o in zip(prompts, budgets, outs):
        ref = np.asarray(sess.generate(jnp.asarray(p)[None], m)[0])
        np.testing.assert_array_equal(o, ref)


def test_prefill_ingestion_matches_token_loop():
    """``generate()`` now ingests the prompt through the cache-populating
    prefill — one parallel forward must reproduce what the old per-token
    teacher-forced replay through ``serve_step`` produced."""
    sess = _session("granite_3_2b")
    prompts = jnp.asarray(np.stack(_prompts(sess, (7, 7, 7))), jnp.int32)
    gen = 6
    new = sess.generate(prompts, gen)

    B, P = prompts.shape
    max_len = P + gen
    caches = sess.init_cache(B, max_len)
    out = [prompts[:, 0]]
    tok = prompts[:, 0]
    for t in range(max_len - 1):   # the pre-scheduler generate() loop
        nxt, caches = sess.serve_step(sess.params, tok, jnp.int32(t), caches)
        tok = prompts[:, t + 1] if t + 1 < P else nxt
        out.append(tok)
    old = jnp.stack(out, axis=1)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_slot_reuse_mid_flight():
    """With 2 slots and 3 requests the third is queued, admitted into the
    slot a short request frees mid-flight, and still decodes exactly."""
    sess = _session("granite_3_2b")
    prompts = _prompts(sess, (6, 6, 6))
    budgets = [12, 2, 8]
    queue = RequestQueue()
    rids = [queue.submit(p, m) for p, m in zip(prompts, budgets)]
    assert len(queue) == 3
    sched = ContinuousBatchingScheduler(sess, n_slots=2, max_len=6 + 12)
    outputs, stats = sched.run(queue)
    assert len(queue) == 0
    assert stats.max_queue_depth == 3
    assert stats.mean_queue_wait_s > 0.0         # request 3 waited for a slot
    # full width while draining: far fewer steps than sequential decode
    assert stats.decode_steps < sum(budgets)
    assert 0.0 < stats.occupancy <= 1.0
    for rid, p, m in zip(rids, prompts, budgets):
        ref = np.asarray(sess.generate(jnp.asarray(p)[None], m)[0])
        np.testing.assert_array_equal(outputs[rid], ref)


class _Tick:
    """A clock that reads one unit later at every call."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


@pytest.mark.parametrize("paged", [False, True], ids=["slots", "paged"])
def test_stats_stamp_steps_and_requests(paged):
    """On a clock that ticks once a call: three requests, two slots.  1 and
    2 are admitted together (ticks 4-6: start, first tokens, end); 2 ends
    after decode step 1 (tick 7); 3 is admitted into its slot (ticks 8-10)
    with budget 1, so it ends at its first token; 1 decodes alone to step
    4 (ticks 11-13)."""
    sess = _session("granite_3_2b")
    clock = _Tick()
    queue = RequestQueue(clock=clock)
    rids = [queue.submit(p, m)
            for p, m in zip(_prompts(sess, (6, 6, 6)), (5, 2, 1))]
    sched = ContinuousBatchingScheduler(sess, n_slots=2, max_len=11,
                                        paged=paged, page_size=4)
    outputs, stats = sched.run(queue, clock=clock)
    assert stats.decode_steps == 4
    assert stats.step_times == [7.0, 11.0, 12.0, 13.0]
    assert stats.admit_s == 4.0
    finish_order = [rids[1], rids[2], rids[0]]
    assert stats.submit_times == [2.0, 3.0, 1.0]
    assert stats.admit_times == [4.0, 8.0, 4.0]
    assert stats.first_token_times == [5.0, 9.0, 5.0]
    assert stats.finish_times() == [7.0, 9.0, 13.0]
    assert stats.token_steps == [(0, 1), (1, 1), (0, 4)]
    for rid, (a, b) in zip(finish_order, stats.token_steps):
        assert len(outputs[rid]) - 6 == 1 + b - a        # first token + steps
    assert stats.mean_queue_wait_s == pytest.approx((2 + 5 + 3) / 3)
    assert stats.mean_ttft_s == pytest.approx((3 + 6 + 4) / 3)
    assert sorted(stats.token_gaps()) == [1.0, 1.0, 2.0, 2.0, 4.0]


@pytest.mark.parametrize("n_slots", [2, 3])
def test_layouts_serve_identically(n_slots):
    """The slot caches and the paged pool are two cache layouts behind one
    loop: a mixed-length request set with a stop token gives the same
    tokens, the same counts and the same number of clock stamps through
    both."""
    sess = _session("granite_3_2b")
    prompts = _prompts(sess, (5, 9, 17, 3, 12), seed=3)
    budgets = [10, 3, 6, 8, 5]
    free = np.asarray(sess.generate(jnp.asarray(prompts[0])[None], 10)[0])
    stop = int(free[len(prompts[0]) + 4])        # request 0 stops early
    (slot_outs, slots), (paged_outs, paged) = [
        sess.serve(prompts, budgets, stop_token=stop, n_slots=n_slots,
                   paged=p, page_size=8) for p in (False, True)]
    assert len(slot_outs[0]) <= len(prompts[0]) + 5
    for a, b in zip(slot_outs, paged_outs):
        np.testing.assert_array_equal(a, b)
    for field in ("requests", "generated_tokens", "decode_steps",
                  "occupancy"):
        assert getattr(slots, field) == getattr(paged, field), field
    assert len(slots.step_times) == len(paged.step_times)
    assert len(slots.token_gaps()) == len(paged.token_gaps())


def test_stop_token_frees_slot_early():
    """A request whose greedy decode hits its stop token ends there: the
    scheduler returns the truncated sequence and the static ``generate``
    pads the finished row with the stop token."""
    sess = _session("granite_3_2b")
    (prompt,) = _prompts(sess, (6,))
    P = len(prompt)
    free = np.asarray(sess.generate(jnp.asarray(prompt)[None], 6)[0])
    gen_toks = free[P:]
    stop = int(gen_toks[2])
    j = int(np.argmax(gen_toks == stop))         # first occurrence ends decode
    outs, stats = sess.serve([prompt], [6], stop_token=stop, n_slots=1)
    np.testing.assert_array_equal(outs[0], free[:P + j + 1])
    assert stats.generated_tokens == j + 1
    padded = np.asarray(sess.generate(jnp.asarray(prompt)[None], 6,
                                      stop_token=stop)[0])
    np.testing.assert_array_equal(padded[:P + j + 1], free[:P + j + 1])
    assert (padded[P + j + 1:] == stop).all()


def test_slot_take_insert_roundtrip():
    """``cache_take_slot`` inverts ``cache_insert_slot`` across the family's
    slot axes — a prefillled width-1 cache written into slot 1 of a width-3
    batch reads back bit-exactly."""
    from repro.core import stepfn
    sess = _session("granite_3_2b")
    (prompt,) = _prompts(sess, (4,))
    _, slot_c = sess.prefill_cache_step(
        sess.params, {"tokens": jnp.asarray(prompt)[None]},
        sess.init_cache(1, 16))
    caches = sess.insert_slot(sess.init_cache(3, 16), slot_c, jnp.int32(1))
    back = stepfn.cache_take_slot(sess.cfg, caches, 1)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(slot_c)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batched_admission_single_prefill_call():
    """All initially-free slots are admitted through ONE batched mixed-length
    prefill (prompts share a bucket), and every output stays token-identical
    to per-request ``generate()``."""
    sess = _session("granite_3_2b")
    lens = (5, 9, 7, 12)                 # mixed lengths, one 16-bucket
    prompts = _prompts(sess, lens)
    budgets = [6, 3, 5, 4]
    calls = []
    inner = sess.prefill_cache_step

    def spy(params, batch, caches):
        calls.append(batch["tokens"].shape)
        return inner(params, batch, caches)

    sess._prefill_cache_step = spy
    try:
        outs, stats = sess.serve(prompts, budgets, n_slots=4, max_len=32)
    finally:
        sess._prefill_cache_step = inner
    assert calls[0] == (4, 16), calls    # one width-4 admission prefill
    assert stats.requests == 4
    for p, m, o in zip(prompts, budgets, outs):
        ref = np.asarray(sess.generate(jnp.asarray(p)[None], m)[0])
        np.testing.assert_array_equal(o, ref)


def test_batched_admission_recurrent_family_groups_exact_lengths():
    """Without padded-prefill support (recurrent caches), equal-length
    prompts still share one batched prefill; unequal ones split."""
    sess = _session("xlstm_125m")
    prompts = _prompts(sess, (6, 6, 9))
    calls = []
    inner = sess.prefill_cache_step

    def spy(params, batch, caches):
        calls.append(batch["tokens"].shape)
        return inner(params, batch, caches)

    sess._prefill_cache_step = spy
    try:
        outs, _ = sess.serve(prompts, [4, 4, 4], n_slots=3, max_len=16)
    finally:
        sess._prefill_cache_step = inner
    assert sorted(calls) == [(1, 9), (2, 6)], calls
    for p, o in zip(prompts, outs):
        ref = np.asarray(sess.generate(jnp.asarray(p)[None], 4)[0])
        np.testing.assert_array_equal(o, ref)


def test_empty_prompt_rejected_at_submit():
    """Regression: ``submit(prompt=[])`` used to be accepted; with bucketing
    the prefill then gathered logits at lengths-1 == -1 (wrapping to a padded
    position → garbage first token), without it the (1, 0) tokens array
    crashed downstream.  Rejected at the API edge now."""
    queue = RequestQueue()
    with pytest.raises(ValueError, match="at least one token"):
        queue.submit(np.zeros(0, np.int32), 4)
    with pytest.raises(ValueError, match="at least one token"):
        queue.submit([], 4)
    assert len(queue) == 0


def test_scheduler_rejects_oversized_request_in_preflight():
    """An impossible request fails BEFORE any decode work — completed
    outputs can't be lost to a mid-drain abort, and the queue is intact."""
    sess = _session("granite_3_2b")
    queue = RequestQueue()
    queue.submit(np.zeros(4, np.int32), 4)       # would fit
    queue.submit(np.zeros(10, np.int32), 10)     # doesn't
    sched = ContinuousBatchingScheduler(sess, n_slots=1, max_len=16)
    with pytest.raises(ValueError, match="exceeds scheduler max_len"):
        sched.run(queue)
    assert len(queue) == 2                       # nothing was popped
    with pytest.raises(ValueError, match="max_new_tokens"):
        queue.submit(np.zeros(4, np.int32), 0)


def test_serve_empty_and_stats():
    sess = _session("granite_3_2b")
    outs, stats = sess.serve([], [])
    assert outs == [] and stats.requests == 0
    (prompt,) = _prompts(sess, (5,))
    _, stats = sess.serve([prompt], [3], n_slots=1)
    assert sess.last_stats is stats and stats.generated_tokens == 3
    assert sess.generate(jnp.asarray(prompt)[None], 0).shape == (1, 5)


def test_prefill_bucketing_bounds_shapes_and_preserves_outputs():
    """Admission prefills are padded to power-of-two buckets: distinct prompt
    lengths hit at most log2(max_len) prefill shapes, and outputs stay
    token-for-token identical to each request decoded alone."""
    sess = _session("granite_3_2b")
    lens = (5, 6, 7, 9, 11, 12)
    prompts = _prompts(sess, lens)
    budgets = [3] * len(prompts)

    shapes = []
    inner = sess.prefill_cache_step

    def spy(params, batch, caches):
        shapes.append(batch["tokens"].shape[1])
        return inner(params, batch, caches)

    sess._prefill_cache_step = spy
    try:
        outs, _ = sess.serve(prompts, budgets, n_slots=2, max_len=32)
    finally:
        sess._prefill_cache_step = inner
    assert set(shapes) == {16}, shapes           # all six lengths → one bucket
    for p, m, o in zip(prompts, budgets, outs):
        ref = np.asarray(sess.generate(jnp.asarray(p)[None], m)[0])
        np.testing.assert_array_equal(o, ref)


def test_cache_zero_slot_resets_to_init_state():
    """``cache_zero_slot`` must return a freed slot to its init-cache state
    (pos → -1, K/V → 0) while leaving every other slot bit-untouched."""
    from repro.core import stepfn
    from repro.models import api as model_api
    sess = _session("granite_3_2b")
    prompts = jnp.asarray(np.stack(_prompts(sess, (6, 6, 6))), jnp.int32)
    _, caches = sess.prefill_cache_step(
        sess.params, {"tokens": prompts}, sess.init_cache(3, 16))
    zeroed = stepfn.cache_zero_slot(sess.cfg, caches, jnp.int32(1))
    fresh = sess.init_cache(3, 16)
    for z, c, f, a in zip(jax.tree_util.tree_leaves(zeroed),
                          jax.tree_util.tree_leaves(caches),
                          jax.tree_util.tree_leaves(fresh),
                          jax.tree_util.tree_leaves(
                              model_api.cache_slot_axes(sess.cfg, caches))):
        z, c, f = np.asarray(z), np.asarray(c), np.asarray(f)
        np.testing.assert_array_equal(np.take(z, 1, axis=a),
                                      np.take(f, 1, axis=a))
        for other in (0, 2):
            np.testing.assert_array_equal(np.take(z, other, axis=a),
                                          np.take(c, other, axis=a))


def test_retired_slot_is_invalidated_before_reuse():
    """Regression: retire used to only clear host state — the freed slot
    kept its K/V until the next admission happened to overwrite it.  Retire
    now zeroes the slot on device, and a request admitted into the
    just-retired slot still decodes exactly."""
    sess = _session("granite_3_2b")
    zero_calls = []
    inner = sess.zero_slot

    def spy(caches, i):
        zero_calls.append(int(i))
        return inner(caches, i)

    sess._zero_slot = spy
    try:
        # n_slots=1 forces request 2 through the slot request 1 just freed
        prompts = _prompts(sess, (7, 5))
        outs, _ = sess.serve(prompts, [4, 6], n_slots=1, max_len=16)
    finally:
        sess._zero_slot = inner
    assert zero_calls == [0, 0], zero_calls      # one invalidation per retire
    for p, m, o in zip(prompts, [4, 6], outs):
        ref = np.asarray(sess.generate(jnp.asarray(p)[None], m)[0])
        np.testing.assert_array_equal(o, ref)


def test_padded_prefill_gate_per_family():
    """Recurrent-state families must NOT bucket (pad tokens would corrupt
    their caches); causal-attention stacks must."""
    dense = ContinuousBatchingScheduler(_session("granite_3_2b"),
                                        n_slots=1, max_len=16)
    ssm = ContinuousBatchingScheduler(_session("xlstm_125m"),
                                      n_slots=1, max_len=16)
    assert dense.bucket_prefills and not ssm.bucket_prefills
    assert dense._bucket_len(5) == 16 and dense._bucket_len(16) == 16
    assert ContinuousBatchingScheduler(
        _session("granite_3_2b"), n_slots=1, max_len=24)._bucket_len(20) == 24
