"""Tiny sizes of the benchmark's configurations, for runs on the CPU."""
import copy
import json

from bench.archs import mamba2, transformer
from bench.harness import ROOT
from bench.models import load_config

SILU_GROUP = {"arch": "transformer", "model_name": "t", "num_hidden_layers": 2, "hidden_size": 64,
              "vocab_size": 512, "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "intermediate_size": 128, "hidden_act": "silu",
              "rope_theta": 10000.0, "rms_norm_eps": 1e-6}
GELU_GROUP = dict(SILU_GROUP, model_name="g", hidden_act="gelu", num_key_value_heads=4)
SSD_GROUP = {"arch": "mamba2", "model_name": "s", "n_layer": 2, "d_model": 64, "vocab_size": 512,
             "d_state": 16, "headdim": 16, "expand": 2, "ngroups": 1, "d_conv": 4,
             "chunk_size": 8, "rms_norm_eps": 1e-6}

TRANSFORMER = transformer.dims(SILU_GROUP)
GELU = transformer.dims(GELU_GROUP)
SSD = mamba2.dims(SSD_GROUP)


def config(name: str = "sneakpeek-granite8b") -> dict:
    """A configuration file's contents with both served models cut to tiny sizes."""
    cfg = load_config(ROOT / "bench" / "configs" / f"{name}.json")
    fast, acc = cfg["roles"]["fast"], cfg["roles"]["accurate"]
    cfg["roles"] = {
        "fast": mamba2.dims(dict(SSD_GROUP, model_name=fast.name)),
        "accurate": transformer.dims(dict(SILU_GROUP if acc.gated else GELU_GROUP,
                                          model_name=acc.name)),
    }
    return cfg


def traffic(per_app: int = 2) -> dict:
    """The paper mix with ``per_app`` requests per application and window."""
    t = copy.deepcopy(json.loads((ROOT / "bench" / "traffic" / "paper.json").read_text()))
    t["per_app_per_window"] = per_app
    t["lead_in_windows"] = 2
    return t
