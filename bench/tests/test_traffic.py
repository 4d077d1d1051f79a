"""The generator gives every seed the same work: the seed draws token ids
only, and the cut-down call that warms up a serving cell admits its
requests exactly as a full call does."""

import numpy as np

from bench import spec, traffic
from bench.drivers import serve
from bench.tests import tiny

SEEDS = (3, 2 ** 33 + 5)


def test_packed_layout_is_the_same_for_every_seed():
    mix = spec.traffic("pack2k")
    a, b = (traffic.train_batch(mix, 4, 1000, s, 1) for s in SEEDS)
    np.testing.assert_array_equal(a["segment_ids"], b["segment_ids"])
    np.testing.assert_array_equal(a["loss_mask"], b["loss_mask"])
    assert not np.array_equal(a["tokens"], b["tokens"])
    other = traffic.train_batch(mix, 4, 1000, SEEDS[0], 2)
    assert not np.array_equal(a["segment_ids"], other["segment_ids"])
    assert (a["segment_ids"][:, -1] == 4).all()   # five documents a row


def test_requests_are_the_same_for_every_seed():
    mix = spec.traffic("decode")
    (pa, ga), (pb, gb) = (traffic.serve_call(mix, 1000, s, 1) for s in SEEDS)
    assert [len(p) for p in pa] == [len(p) for p in pb]
    assert ga == gb == sorted(ga, reverse=True)
    assert any(not np.array_equal(x, y) for x, y in zip(pa, pb))
    k = mix["warmup_divisor"]
    short = traffic.request_sizes(mix, warmup=True)
    assert [p for p, _ in short] == [len(p) for p in pa]
    assert [(m - 1) // k + 1 for m in ga] == [m for _, m in short]


def test_warmup_call_compiles_every_shape_of_a_full_call():
    """At the cell's own sizes (tiny widths): after the cut-down call, a
    full call compiles nothing, and it admitted more decode steps' work."""
    job = tiny.job(tiny.SERVE, real_traffic=True)
    inf = serve.build(job)
    *_, short = serve.call(job, inf, 0, warmup=True)
    job.counter.count, job.counter.armed = 0, True
    *_, full = serve.call(job, inf, 1)
    job.counter.armed = False
    assert job.counter.count == 0
    k = job.mix["warmup_divisor"]
    assert full.decode_steps == k * short.decode_steps
    assert full.occupancy == short.occupancy == 1.0
