"""Largest over mean load of a held expert in a train step's layer, over
the window's steps."""


def read(collected):
    train = collected.get("train") or {}
    experts, model = train.get("experts"), train.get("model")
    if not experts or not model or not experts.get("held"):
        return None
    return experts["load_max"] * len(model["experts_held"]) / experts["held"]
