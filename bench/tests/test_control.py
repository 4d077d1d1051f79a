"""The control at a size a test run holds: the reference computed in fp8 in
the program's place has to come out not correct under each cell's limits.
(On the chip, at the cells' own sizes, ``bench/calibrate.py --what control``
reads the same numbers; PERF.md lists them.)"""

from bench import correct
from bench.drivers import serve, train
from bench.tests import tiny


def test_fp8_control_fails_the_train_limits():
    job = tiny.job(tiny.TRAIN)
    from bench import traffic
    host = [traffic.train_batch(job.mix, job.conf["train"]["batch"],
                                job.model_cfg.vocab_size, job.seed, i)
            for i in range(train.CHECK_STEPS)]
    ref = train.reference(job, host)
    ctl = train.reference(job, host, "fp8")
    ok, checks = correct.judge(correct.train_numbers(ctl, ref), job.limits)
    assert not ok, checks


def test_fp8_control_fails_the_serve_limit():
    # the served-token gap grows with width and depth: at the cell's own
    # widths 4 layers hold it well above the limit (0.38-0.51 on two seeds
    # on the CPU), where at tiny widths fp8 reads about the limit itself
    job = tiny.job(tiny.SERVE, full_width=True, n_layers=4)
    inf = serve.build(job)
    done = [serve.call(job, inf, 0)]
    _, failed, seqs = serve.sample(job, done)
    assert failed == 0
    gaps = serve.reference(job, seqs, ("f32", "fp8"))
    ok, checks = correct.judge({"logit_gap": correct.widest(gaps["fp8"])},
                               job.limits)
    assert not ok, checks
