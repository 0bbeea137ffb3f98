"""Small configurations and mixes that a CPU test run can hold."""
import copy
import json

from conftest import BENCH
from drivers.lm_serving import as_run


def config(name: str, **kw) -> dict:
    """A configuration file at smoke widths, as run."""
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    small = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, vocab_size=256,
                 intermediate_size=128)
    if c.get("num_local_experts"):
        small.update(num_local_experts=8, num_experts_per_tok=4,
                     intermediate_size=64)
    c.update(small, **kw)
    if "attention_multiplier" in c.get("run_as", {}):
        c["run_as"] = dict(c["run_as"], attention_multiplier=16 ** -0.5)
    return as_run(c)


def mix(rate: float = 20.0, **kw) -> dict:
    m = {"arrivals": {"process": "poisson", "rate_per_s": rate},
         "prompt_len": {"choices": [16, 40], "weights": [1, 1]},
         "output_len": {"dist": "uniform", "min": 4, "max": 12},
         "slots": 4, "cache_len": 64, "base_seed": 0, "preroll_s": 0.5,
         "check": {"served_tokens": 40, "max_requests": 6, "min_requests": 3}}
    m.update(copy.deepcopy(kw))
    return m
