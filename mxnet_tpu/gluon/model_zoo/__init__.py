from . import text
from . import vision


def get_model(name, **kwargs):
    """A model of the zoo by name: the vision models, and the language
    models of ``model_zoo.text`` (built from a configuration)."""
    zoo = text if name.lower() in text._models else vision
    return zoo.get_model(name, **kwargs)
