"""A cell at a size a CPU test can run: the configuration files' keys and
structure, with tiny widths."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from cell import DEFAULT_FAMILY, Cell, load_family

HERE = Path(__file__).resolve().parents[1]

TINY_SIZES = {"hidden_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 4, "head_dim": 32,
              "intermediate_size": 256, "vocab_size": 512}
TINY_SERVE = {"capacity": 4, "max_len": 192, "prefill_chunk": 64,
              "prefill_bucket": 16}


def tiny_conf(name: str, layers: int = 2, sizes: dict = TINY_SIZES) -> dict:
    """The configuration file ``name`` with ``sizes`` (the llama family's
    widths by default; a family's tests pass its own keys) and ``layers``
    layers, or two physical blocks under a reuse plan."""
    conf = json.loads((HERE / "configs" / f"{name}.json").read_text())
    conf.update(sizes)
    if conf.get("reuse"):
        conf["reuse"] = dict(conf["reuse"], num_basic=2)
        conf["num_hidden_layers"] = 2 * conf["reuse"]["reuse_times"]
    else:
        conf["num_hidden_layers"] = layers
    return conf


def tiny_traffic(loop: str = "closed") -> dict:
    t = {"loop": loop, "population": 16,
         "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                        "min": 8, "max": 128},
         "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                        "min": 2, "max": 16},
         "serve": dict(TINY_SERVE)}
    if loop == "closed":
        t["clients"] = 4
    else:
        t["rate_per_s"] = 4.0
    return t


def tiny_cell(name: str = "deepseek-7b", loop: str = "closed",
              limit: float = 0.5, execution: str = "photonic") -> Cell:
    conf = tiny_conf(name)
    conf["execution"] = execution
    return Cell(name=f"tiny.{name}", chips=1, conf=conf,
                family=load_family(conf.get("model_type", DEFAULT_FAMILY)),
                traffic=tiny_traffic(loop),
                limits={"max_logit_gap": {"limit": limit}},
                end_to_end=copy.deepcopy(END_TO_END), per_layer=[])


END_TO_END = [{"name": n, "unit": u} for n, u in (
    ("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"),
    ("output_tok_s", "tokens/s"), ("setup_s", "s"))]
