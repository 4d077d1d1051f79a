"""Serving cells: offline generation through ``InferenceSession.serve`` on
the paged KV pool, on the default decode path.

Each call serves one closed batch of requests.  Every call has the same
sizes in the same order, from the mix; only the token ids differ.  Set-up
serves one call with every output cut k-fold (``traffic.request_sizes``),
which the scheduler admits in the same groups, so it compiles every prefill
and decode shape the window uses.  The window is the whole calls that start
before ``--seconds`` have passed.  Afterwards a sample of the finished
requests, drawn from the seed and holding the longest, is run through the
reference.
"""

from __future__ import annotations

import numpy as np

from bench import correct, harness, spec, traffic, weights

SAMPLE = 3          # requests the reference checks: the longest and 2 more


def build(job):
    """The serving session on the seed's weights, in the type served."""
    import jax
    from repro.session import InferenceSession
    from repro.models import api as model_api

    cfg = job.model_cfg
    dtype = cfg.compute_dtype
    abstract = jax.eval_shape(lambda: model_api.init_params(
        cfg, jax.random.PRNGKey(0)))
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, dtype), abstract)
    params = weights.make(abstract, job.seed, cfg.n_layers, dtype,
                          jax.sharding.SingleDeviceSharding(job.devices[0]))
    return InferenceSession.from_params(cfg, params)


def call(job, inf, i, warmup=False):
    """Serve call ``i`` of the run: (prompts, max_new_tokens, outputs,
    stats)."""
    srv = job.conf["serve"]
    prompts, gens = traffic.serve_call(job.mix, job.model_cfg.vocab_size,
                                       job.seed, i, warmup)
    outs, stats = inf.serve(prompts, gens, n_slots=srv["n_slots"],
                            max_len=traffic.max_len(job.mix), paged=True,
                            page_size=srv["page_size"])
    return prompts, gens, outs, stats


def sample(job, done) -> tuple:
    """(finished requests as (tokens, prompt_len, max_new), failed count,
    the sample the reference checks: the longest and SAMPLE-1 drawn from
    the seed)."""
    finished = [(o, len(p), g) for prompts, gens, outs, _ in done
                for o, p, g in zip(outs, prompts, gens)]
    failed = sum(len(o) != p + g for o, p, g in finished)
    longest = max(range(len(finished)), key=lambda j: finished[j][2])
    others = [j for j in traffic.rng(job.seed, 5).permutation(len(finished))
              if j != longest]
    pick = [longest] + [int(j) for j in others[:SAMPLE - 1]]
    return finished, failed, [(np.asarray(finished[j][0]), finished[j][1])
                              for j in pick]


def reference(job, seqs, precisions=("f32",)):
    return spec.reference(job.conf).serve_gaps(
        job.conf["model"], job.seed, seqs, job.model_cfg.compute_dtype,
        precisions, eps=job.conf["norm_eps"])


def run(job) -> dict:
    inf = build(job)
    call(job, inf, 0, warmup=True)            # compiles every shape
    job.setup_done()
    done = []
    with job.window() as win:
        i = 1
        while not done or win.elapsed() < job.seconds:
            done.append(call(job, inf, i))
            i += 1
    window_s = win.seconds
    peak = harness.peak_bytes(job.devices[:1])
    harness.free(inf.params)
    inf = None

    finished, failed, seqs = sample(job, done)
    numbers = {"logit_gap": correct.widest(reference(job, seqs)["served"])}

    stats = [s for *_, s in done]
    generated = sum(s.generated_tokens for s in stats)
    return {
        "numbers": numbers,
        "attempted": len(finished), "failed": failed,
        "e2e": {"serve_tokens_per_s": generated / window_s},
        "memory_peak_bytes": peak,
        "ctx": {"driver": "serve", "window_s": window_s, "chips": 1,
                "calls": len(done), "generated": generated,
                "decode_steps": sum(s.decode_steps for s in stats),
                "occupancy_steps": sum(s.occupancy * s.decode_steps
                                       for s in stats)},
    }
