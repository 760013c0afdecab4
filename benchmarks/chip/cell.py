"""One cell of the benchmark: its entry in ``BENCHMARK.json``, its model
configuration file and its traffic file, all found by name."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# a configuration file's MLP activation (the published config.json's
# ``hidden_act``: a gated MLP whose gate takes it) -> the program's mlp_act
MLP_ACT = {"silu": "swiglu"}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    conf: dict           # configs/<config>.json
    traffic: dict        # traffic/<traffic>.json
    limits: dict         # checks/<workload>.json
    end_to_end: list     # BENCHMARK.json metrics this cell reports
    per_layer: list


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload``; raises KeyError when BENCHMARK.json has none."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfile = {c["name"]: c for c in bench["configs"]}[entry["config"]]["file"]
    conf = json.loads((root / cfile).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{entry['traffic']}.json").read_text())
    limits = json.loads((HERE / "checks" / f"{workload}.json").read_text())

    def mine(m):
        return workload in m.get("workloads", [workload])
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if mine(m) and m["moves"] in names]
    return Cell(name=workload, chips=int(entry["chips"]), conf=conf,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=layer)


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file.  The file is the
    truth: every size is taken from it, and a feature the program cannot run
    as the file states is an error, not a silent substitution."""
    from repro.configs import get_arch
    from repro.core.prm import ReuseConfig

    base = get_arch(conf["architecture"])
    act = MLP_ACT.get(conf["hidden_act"])
    if base.mlp_act != act or base.norm != conf["norm"]:
        raise ValueError(f"{conf['name']}: the program's {base.name} runs "
                         f"{base.mlp_act}/{base.norm}, the file states "
                         f"{conf['hidden_act']}/{conf['norm']}")
    if conf.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError("the program applies RoPE to the whole head")
    if conf.get("tie_word_embeddings"):
        raise ValueError("the program keeps its own unembedding")
    reuse = None
    if conf.get("reuse"):
        r = conf["reuse"]
        reuse = ReuseConfig(granularity="block", num_basic=r["num_basic"],
                            reuse_times=r["reuse_times"],
                            transforms=tuple(r["transforms"]),
                            shuffle_groups=r["shuffle_groups"],
                            shuffle_block=0)
    return dataclasses.replace(
        base, name=conf["name"], num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        padded_vocab=0, rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["norm_eps"]), compute_dtype=conf["dtype"],
        param_dtype=conf["dtype"], execution=conf["execution"], reuse=reuse)
