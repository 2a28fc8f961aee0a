"""Cells at a size a CPU test run can hold: the widths of the real
configuration cut down, the traffic shortened, everything else the
committed files' own."""
import json
import time

from bench.lib import harness, traffic

ROOT = harness.ROOT
SEED = 2**31 + 4242


def spec():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


# a cell BENCHMARK.json does not hold yet (PERF.md, Open questions):
# its mix and runner path are kept working at CPU size
LM_CELLS = [{"name": "lm_qwen25_3b.docqa_backlog", "config": "qwen2.5-3b",
             "traffic": "docqa_backlog", "chips": 1}]


def cell(name):
    cells = spec()["workloads"] + LM_CELLS
    return next(w for w in cells if w["name"] == name)


def config(name):
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        c = json.load(f)
    if c["runner"] == "lm_batcher":
        c.update(hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, intermediate_size=128,
                 vocab_size=512, num_hidden_layers=2)
    else:
        c["input_hw"] = 20
    return c


def mix(name):
    m = traffic.load(name)
    if m["kind"] == "open_loop":
        m.update(rate_per_s=4.0, ramp_s=0.5)
        m["prompt_len"]["grid"] = [8, 16, 32]
        m["output_len"].update(min=3, max=24, median=8)
        m["server"] = {"n_slots": 4, "max_len": 64}
    elif m["kind"] == "backlog":
        m.update(n_requests=24)
        m["prompt_len"]["values"] = [16, 32, 48]
        m["output_len"].update(min=3, max=12)
        m["server"] = {"n_slots": 4, "max_len": 64}
    else:
        m.update(image_hw=20, distinct_batches=2)
    return m


def run(cell_name, *, seconds=2.0, trace=False, control=False):
    c = cell(cell_name)
    return harness.run_cell(
        spec(), c, seed=SEED, seconds=seconds, trace=trace,
        t_start=time.monotonic(), clock=harness.CompileClock(),
        config=config(c["config"]), mix=mix(c["traffic"]),
        control=control)
