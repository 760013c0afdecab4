"""One cell of the benchmark: its entry in ``BENCHMARK.json``, its model
configuration file, the configuration's family, its traffic file and its
check limits, all found by name."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
FAMILIES = HERE / "families"
# the family of a configuration file that states no published model_type
DEFAULT_FAMILY = "llama"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    conf: dict           # configs/<config>.json
    family: ModuleType   # families/<model_type>.py
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
    family = load_family(conf.get("model_type", DEFAULT_FAMILY))
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
                family=family, traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=layer)


def load_family(model_type: str) -> ModuleType:
    """``families/<model_type>.py``, loaded by path once per process.  A
    family file gives:

    - ``program_config(conf)``: the program's ModelConfig, every size from
      the file; raises on anything the program cannot run as it states;
    - ``logits(conf, seed, seqs, rows, bits=None, shape=None)``: the plain
      float32 reference's logits of ``seqs`` at ``rows`` (``reference.py``
      holds the shared pieces); ``bits`` is the control;
    - ``token_matmuls(conf)``: (K, N) of every W8A8 matmul one token passes
      through, in order, ``workcount.unembedding(conf)`` last;
    - ``attention_call(q_len, q_offset, conf, peaks)``: one logical layer's
      attention as a ``workcount.Work``.

    Raises FileNotFoundError, naming the file, for a model type that has
    none."""
    if not re.fullmatch(r"[A-Za-z0-9_]+", model_type):
        raise ValueError(f"model_type {model_type!r} is not a family name")
    path = FAMILIES / f"{model_type}.py"
    if not path.is_file():
        raise FileNotFoundError(f"model_type {model_type!r} has no family "
                                f"file: {path} does not exist")
    name = f"family_{model_type}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]
