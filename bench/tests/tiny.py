"""Tiny sizes of the benchmark's configurations, for runs on the CPU."""
import copy
import dataclasses
import json

from bench.harness import ROOT
from bench.models import Dims, load_config

TRANSFORMER = Dims(kind="transformer", name="t", layers=2, d=64, vocab=512, heads=4, kv_heads=2,
                   head_dim=16, ff=128, act="silu")
GELU = dataclasses.replace(TRANSFORMER, name="g", act="gelu", kv_heads=4)
SSD = Dims(kind="ssd", name="s", layers=2, d=64, vocab=512, d_state=16, headdim=16, expand=2,
           ngroups=1, d_conv=4, chunk=8)


def config(name: str = "sneakpeek-granite8b") -> dict:
    """A configuration file's contents with both served models cut to tiny sizes."""
    cfg = load_config(ROOT / "bench" / "configs" / f"{name}.json")
    fast, acc = cfg["roles"]["fast"], cfg["roles"]["accurate"]
    cfg["roles"] = {
        "fast": dataclasses.replace(SSD, name=fast.name),
        "accurate": dataclasses.replace(TRANSFORMER if acc.gated else GELU, name=acc.name),
    }
    return cfg


def traffic(per_app: int = 2) -> dict:
    """The paper mix with ``per_app`` requests per application and window."""
    t = copy.deepcopy(json.loads((ROOT / "bench" / "traffic" / "paper.json").read_text()))
    t["per_app_per_window"] = per_app
    t["lead_in_windows"] = 2
    return t
