"""Language models built from a published configuration (a dict with the
keys of the model's ``config.json``)."""
from .deepseek_v3 import DeepseekV3, deepseek_v3
from .laguna import Laguna, laguna
from .lfm2_moe import LFM2MoE, lfm2_moe
from .qwen3_next import Qwen3Next, qwen3_next
from .smallthinker import SmallThinker, smallthinker

_models = {"deepseek_v3": deepseek_v3, "laguna": laguna,
           "lfm2_moe": lfm2_moe, "qwen3_next": qwen3_next,
           "smallthinker": smallthinker}


def get_model(name, **kwargs):
    name = name.lower()
    if name not in _models:
        raise ValueError(
            f"model {name!r} not in model zoo; available: {sorted(_models)}")
    return _models[name](**kwargs)
