"""Packed-sequence training end to end: the data pipeline packs EOS-delimited
documents into fixed rows with ``segment_ids``; every sdpa path (einsum /
chunked / flash kernel) shares the segment mask; packed-batch loss equals the
per-document unpacked loss; and the pipeline-parallel path threads segments
per micro-batch.  Plus regression tests for the MemmapLM windowing bug and
the sdpa bias/causal footgun fixed alongside."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfg_mod
from repro.data import DataConfig, MemmapLM, SyntheticLM, pack_segments
from repro.models import api as model_api
from repro.runtime import flags

KEY = jax.random.PRNGKey(0)
EOS = 0


# ---------------------------------------------------------------------------
# data pipeline: pack_documents
# ---------------------------------------------------------------------------

def _check_packed_batch(b, S):
    tok, seg, mask = b["tokens"], b["segment_ids"], b["loss_mask"]
    assert seg.shape == tok.shape == mask.shape == b["labels"].shape
    assert seg.dtype == np.int32
    # ids are monotone within a row and increment exactly after an EOS
    assert (np.diff(seg, axis=1) >= 0).all()
    np.testing.assert_array_equal(np.diff(seg, axis=1) == 1,
                                  tok[:, :-1] == EOS)
    # the loss mask zeroes exactly the cross-document labels (EOS positions
    # predict the next document's first token); EOS itself stays a target
    np.testing.assert_array_equal(mask == 0.0, tok == EOS)


def test_synthetic_packed_batch():
    ds = SyntheticLM(DataConfig(seq_len=64, global_batch=4,
                                pack_documents=True, eos_id=EOS), vocab=97)
    b = ds.batch(3)
    _check_packed_batch(b, 64)
    assert b["segment_ids"].max() >= 1          # actually multi-document
    # deterministic: batch is a pure function of step
    np.testing.assert_array_equal(b["tokens"], ds.batch(3)["tokens"])


def test_memmap_packed_batch(tmp_path):
    rng = np.random.RandomState(0)
    data = rng.randint(1, 200, size=5000).astype(np.uint32)
    data[::13] = EOS                            # EOS-delimited documents
    path = tmp_path / "toks.bin"
    data.tofile(path)
    ds = MemmapLM(DataConfig(seq_len=32, global_batch=4, path=str(path),
                             pack_documents=True, eos_id=EOS), vocab=256)
    b = ds.batch(1)
    _check_packed_batch(b, 32)
    # labels are still the shifted stream
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


def test_pack_segments_label_alignment():
    rows = np.array([[5, 6, EOS, 7, 8, 9, EOS, 4, 3]])
    b = pack_segments(rows, EOS)
    np.testing.assert_array_equal(b["segment_ids"],
                                  [[0, 0, 0, 1, 1, 1, 1, 2]])
    np.testing.assert_array_equal(b["loss_mask"],
                                  [[1, 1, 0, 1, 1, 1, 0, 1]])
    np.testing.assert_array_equal(b["tokens"], [[5, 6, EOS, 7, 8, 9, EOS, 4]])
    np.testing.assert_array_equal(b["labels"], [[6, EOS, 7, 8, 9, EOS, 4, 3]])


# ---------------------------------------------------------------------------
# MemmapLM windowing regression (satellite bugfix)
# ---------------------------------------------------------------------------

def _window_file(tmp_path, n_tokens, seq_len):
    data = np.arange(n_tokens, dtype=np.uint32)
    path = tmp_path / "w.bin"
    data.tofile(path)
    return str(path)


def test_memmap_windowing_covers_all_windows(tmp_path):
    """Old code used ``% (n_windows - B)``: the last B windows were never a
    base, and n_windows <= B degenerated to base=0 (every step identical)."""
    S, B = 8, 4
    path = _window_file(tmp_path, (S + 1) * 6, S)   # 6 windows, batch 4
    ds = MemmapLM(DataConfig(seq_len=S, global_batch=B, path=str(path)),
                  vocab=1 << 30)
    firsts = {int(r[0]) for step in range(3) for r in ds.batch(step)["tokens"]}
    assert len(firsts) == 6                          # every window visited
    # consecutive steps are NOT the stuck base=0 batch the old modulo
    # produced whenever n_windows <= B + 1
    assert not np.array_equal(ds.batch(0)["tokens"], ds.batch(1)["tokens"])


def test_memmap_windowing_host_shards_disjoint(tmp_path):
    S, G = 8, 4
    path = _window_file(tmp_path, (S + 1) * 7, S)    # 7 windows (prime-ish)
    hosts = [MemmapLM(DataConfig(seq_len=S, global_batch=G, path=path,
                                 host_id=h, num_hosts=2), vocab=1 << 30)
             for h in (0, 1)]
    for step in range(9):                            # crosses several wraps
        t0 = hosts[0].batch(step)["tokens"]
        t1 = hosts[1].batch(step)["tokens"]
        starts0 = {int(r[0]) for r in t0}
        starts1 = {int(r[0]) for r in t1}
        assert not starts0 & starts1, (step, starts0, starts1)


def test_memmap_too_small_raises(tmp_path):
    S = 8
    path = _window_file(tmp_path, (S + 1) * 3, S)    # 3 windows < batch 4
    with pytest.raises(ValueError, match="cannot fill one global batch"):
        MemmapLM(DataConfig(seq_len=S, global_batch=4, path=path), vocab=1 << 30)


# ---------------------------------------------------------------------------
# packed loss == per-document unpacked loss (the tentpole invariant)
# ---------------------------------------------------------------------------

def _packed_and_docs(cfg, lens, S, seed=0):
    rng = np.random.RandomState(seed)
    docs = [rng.randint(1, cfg.vocab_size, size=l).astype(np.int32)
            for l in lens]
    row = np.concatenate(docs)
    assert len(row) == S
    seg = np.concatenate([np.full(l, i, np.int32)
                          for i, l in enumerate(lens)])
    labels = np.concatenate([row[1:], [0]]).astype(np.int32)
    mask = np.ones(S, np.float32)
    mask[np.cumsum(lens) - 1] = 0.0                 # cross-doc + final label
    packed = {"tokens": jnp.asarray(row[None]),
              "labels": jnp.asarray(labels[None]),
              "loss_mask": jnp.asarray(mask[None]),
              "segment_ids": jnp.asarray(seg[None])}
    return packed, docs


def _doc_loss(cfg, params, docs):
    """Token-weighted mean of each document trained alone."""
    tot, cnt = 0.0, 0
    for d in docs:
        batch = {
            "tokens": jnp.asarray(d[None]),
            "labels": jnp.asarray(np.concatenate([d[1:], [0]])[None]
                                  .astype(np.int32)),
            "loss_mask": jnp.asarray(
                np.concatenate([np.ones(len(d) - 1), [0.0]])[None]
                .astype(np.float32)),
        }
        loss, _ = model_api.loss_fn(cfg, params, batch)
        tot += float(loss) * (len(d) - 1)
        cnt += len(d) - 1
    return tot / cnt


@pytest.mark.parametrize("arch,window", [
    ("granite_3_2b", None),      # dense GQA
    ("granite_3_2b", 8),         # + sliding window
])
def test_packed_loss_matches_unpacked(arch, window):
    cfg = cfg_mod.get_config(arch).reduced()
    if window is not None:
        cfg = dataclasses.replace(cfg, swa_window=window)
    params = model_api.init_params(cfg, KEY)
    packed, docs = _packed_and_docs(cfg, (12, 9, 11), 32)
    loss_p, _ = model_api.loss_fn(cfg, params, packed)
    # RoPE is relative — a document's scores only depend on i - j, so the
    # packed offset is numerically immaterial (fp tolerance only)
    np.testing.assert_allclose(float(loss_p), _doc_loss(cfg, params, docs),
                               rtol=5e-5)


def test_packed_moe_loss_finite_and_masked():
    """MoE capacity routing is batch-shape dependent (different tokens drop
    when documents share a row), so exact per-doc equivalence cannot hold —
    but the segment mask must still thread through the attention halves and
    train finitely."""
    cfg = cfg_mod.get_config("olmoe_1b_7b").reduced()
    params = model_api.init_params(cfg, KEY)
    packed, docs = _packed_and_docs(cfg, (12, 9, 11), 32)
    loss_p, m = model_api.loss_fn(cfg, params, packed)
    assert np.isfinite(float(loss_p)) and float(m["aux"]) > 0.0
    # routing noise is small at this scale: packed stays near per-doc
    np.testing.assert_allclose(float(loss_p), _doc_loss(cfg, params, docs),
                               rtol=5e-2)


def test_packed_loss_flash_path_matches_reference():
    """Forcing the Pallas kernel on (interpret mode) must not change the
    packed loss or its gradients — packed training takes the tiled path."""
    cfg = cfg_mod.get_config("granite_3_2b").reduced()
    params = model_api.init_params(cfg, KEY)
    packed, _ = _packed_and_docs(cfg, (12, 9, 11), 32)

    def loss(p):
        return model_api.loss_fn(cfg, p, packed)[0]

    base, gbase = jax.value_and_grad(loss)(params)
    with flags.flag_ctx(flash_attention=True):
        fast, gfast = jax.value_and_grad(loss)(params)
    np.testing.assert_allclose(float(base), float(fast), rtol=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gbase),
                    jax.tree_util.tree_leaves(gfast)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)


def test_packed_pipeline_loss_matches_plain():
    """pp > 1: segment ids re-indexed per (stage, superstep) — the pipeline
    must produce the same packed loss as the plain stacked model."""
    from repro.core.pipeline import pipeline_loss, stack_for_pipeline
    from repro.core.recipe import ParallelismConfig
    cfg = cfg_mod.get_config("granite_3_2b").reduced()
    params = model_api.init_params(cfg, KEY)
    rows = []
    for i in range(8):
        packed, _ = _packed_and_docs(cfg, (12, 9, 11), 32, seed=i)
        rows.append(packed)
    batch = {k: jnp.concatenate([r[k] for r in rows]) for k in rows[0]}
    ref, _ = model_api.loss_fn(cfg, params, batch)
    plan = ParallelismConfig(pp=2, gas=4)
    pparams = dict(params, blocks=stack_for_pipeline(params["blocks"], 2))
    got, _ = pipeline_loss(cfg, pparams, batch, plan)
    np.testing.assert_allclose(float(ref), float(got), rtol=1e-5)


def test_recurrent_blocks_reject_segments():
    cfg = cfg_mod.get_config("xlstm_125m").reduced()
    params = model_api.init_params(cfg, KEY)
    packed, _ = _packed_and_docs(
        dataclasses.replace(cfg, vocab_size=cfg.vocab_size), (12, 9, 11), 32)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        model_api.loss_fn(cfg, params, packed)


# ---------------------------------------------------------------------------
# mask semantics shared by all sdpa paths
# ---------------------------------------------------------------------------

def _qkv(B, S, Hq, Hkv, D):
    q = jax.random.normal(KEY, (B, S, Hq, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, D))
    return q, k, v


def _random_segments(B, S, n_docs, seed=0):
    rng = np.random.RandomState(seed)
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        cuts = np.sort(rng.choice(np.arange(1, S), n_docs - 1, replace=False))
        seg[b] = np.searchsorted(cuts, np.arange(S), side="right")
    return jnp.asarray(seg)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 16),
                                           (False, None)])
def test_chunked_sdpa_matches_einsum_with_segments(causal, window):
    from repro.models.attention import chunked_sdpa, sdpa
    q, k, v = _qkv(2, 96, 4, 2, 16)
    seg = _random_segments(2, 96, 4)
    want = sdpa(q, k, v, None, causal=causal, window=window, segment_ids=seg)
    got = chunked_sdpa(q, k, v, causal=causal, window=window,
                       segment_ids=seg, bq=32, bk=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_sdpa_bias_composes_with_causal():
    """Regression: ``bias`` used to silently DISABLE causal/window masking
    (an ``elif``) — a caller passing both got bidirectional attention."""
    from repro.models.attention import sdpa
    q, k, v = _qkv(1, 16, 2, 2, 8)
    zero_bias = jnp.zeros((1, 16, 16), jnp.float32)
    causal_only = sdpa(q, k, v, None, causal=True)
    both = sdpa(q, k, v, zero_bias, causal=True)
    np.testing.assert_allclose(np.asarray(both), np.asarray(causal_only),
                               atol=1e-6, rtol=1e-6)
    # and a real bias still applies on top of the synthesized mask
    bias = jax.random.normal(jax.random.fold_in(KEY, 9), (1, 16, 16))
    biased = sdpa(q, k, v, bias, causal=True)
    assert not np.allclose(np.asarray(biased), np.asarray(causal_only))


def test_flash_supported_with_segments():
    from repro.kernels import ops
    q, k, _ = _qkv(1, 128, 2, 2, 16)
    seg = _random_segments(1, 128, 3)
    assert ops.flash_supported(q, k, causal=True, segment_ids=seg)
    # segment masks need aligned self-attention
    q_short = q[:, :64]
    assert not ops.flash_supported(q_short, k, causal=False, segment_ids=seg)


# ---------------------------------------------------------------------------
# packed training smoke: the tiled path actually trains
# ---------------------------------------------------------------------------

def test_packed_training_loss_decreases():
    from repro.core import stepfn
    from repro.session import TrainSession
    sess = TrainSession.from_recipe(
        "granite_3_2b", reduced=True,
        train_cfg=stepfn.TrainConfig(peak_lr=1e-3, warmup=5, total_steps=40),
        data_cfg=DataConfig(seq_len=64, global_batch=8,
                            pack_documents=True, eos_id=EOS))
    first = float(sess.step()["loss"])
    for _ in range(39):
        m = sess.step()
    last = float(m["loss"])
    assert np.isfinite(last) and last < first - 0.02, (first, last)
