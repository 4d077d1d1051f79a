"""Small jobs that drive a whole run on the CPU: the cells' own harness,
traffic generator, reference and limits, at widths a test run holds (and,
unless ``real_traffic``, at small sizes of the cell's mix)."""

import copy

import jax

from bench import harness, spec
from bench.run import Job

TRAIN = "granite_3_2b_d8.train.pack2k"
TRAIN_MESH = "granite_3_2b.train.pp2tp2"
SERVE = "granite_3_2b.serve.decode"
WIDTHS = dict(d_model=512, n_heads=8, n_kv_heads=2, head_dim=64, d_ff=1024,
              vocab_size=512)


def job(workload: str, seed: int = 2 ** 33 + 11, *, real_traffic: bool = False,
        full_width: bool = False, n_layers: int = 2) -> Job:
    bench = spec.benchmark()
    cell = spec.cell(workload, bench)
    conf = copy.deepcopy(spec.config_file(cell["config"], bench))
    conf["model"].update(n_layers=n_layers, **({} if full_width else WIDTHS))
    mix = copy.deepcopy(spec.traffic(cell["traffic"]))
    if real_traffic:
        pass                        # the mix as the cell sends it
    elif mix["driver"] == "train":
        conf["train"]["batch"] = conf["train"]["plan"].get("gas", 2)
        mix.update(seq_len=128, doc_mean=32, distinct_batches=3)
    else:
        conf["serve"].update(n_slots=4, page_size=16)
        mix.update(requests_per_call=6, warmup_divisor=4,
                   prompt={"dist": "loguniform", "min": 8, "max": 40},
                   output={"dist": "uniform", "min": 8, "max": 30})
    return Job(bench, workload, seed, 1, False, jax.devices(),
               harness.CompileCounter(), conf=conf, mix=mix)
