"""Record ``data/v5e_train_toy.xplane.pb``, the chip trace ``test_named.py``
reads: the benchmark's tracer around three steps of ``TrainSession.run`` on
a Granite-shaped toy (2 layers, d_model 256, 4/2 heads of 64, d_ff 512,
vocab 1024) fed one 2048-token row of the ``pack2k`` mix, so the
segment-masked flash kernels and full remat run; ``log_every=2`` logs one
step.  On one TPU chip:

    PYTHONPATH=src python -m bench.tests.record_train_trace <out.xplane.pb>

``trim(src, dst)`` keeps what the reductions read, and runs anywhere: the
host's plane, and of the chip's its ``XLA Ops`` line, each op's name as
far as `` = `` and its ``tf_op`` and ``hlo_category`` stats (the HLO text,
source lines and shapes took 3/4 of the bytes).
"""

from __future__ import annotations

import dataclasses
import shutil
import sys

KEPT_STATS = ("tf_op", "hlo_category")


def record(out: str) -> None:
    import jax
    from bench import harness, spec, trace, traffic
    from repro.core import stepfn
    from repro.session import TrainSession

    cfg = dataclasses.replace(
        spec.model_config(spec.config_file("granite_3_2b_d8")),
        n_layers=2, d_model=256, n_heads=4, n_kv_heads=2, d_ff=512,
        vocab_size=1024)
    batch = jax.device_put(traffic.train_batch(spec.traffic("pack2k"), 1,
                                               cfg.vocab_size, 0, 0))
    sess = TrainSession(cfg, train_cfg=stepfn.TrainConfig(
        peak_lr=1e-3, warmup=10, total_steps=100))
    sess.batches = lambda step: batch
    sess.run(2, log_every=2, log=lambda s: None)          # compiles
    sess._next_step = 0                                    # run again
    with harness.Tracer("toy") as tracer:
        sess.run(3, log_every=2, log=lambda s: None)
        jax.block_until_ready(sess.state)
    shutil.copy(trace.find_xplane(tracer.dir), out)


def trim(src: str, dst: str) -> None:
    from bench import named, trace
    space = named.read_xspace(src)
    for i in reversed(range(len(space.planes))):
        if space.planes[i].name not in ("/device:TPU:0", trace.HOST_PLANE):
            del space.planes[i]
    for pl in space.planes:
        if pl.name == trace.HOST_PLANE:
            continue
        for i in reversed(range(len(pl.lines))):
            if pl.lines[i].name != trace.OPS_LINE:
                del pl.lines[i]
        stat = {e.key: e.value.name for e in pl.stat_metadata}
        for e in pl.event_metadata:
            md = e.value
            md.name = md.name.split(" = ", 1)[0] + " = "
            kept = [st for st in md.stats
                    if stat.get(st.metadata_id) in KEPT_STATS]
            del md.stats[:]
            md.stats.extend(kept)
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    record(sys.argv[1])
