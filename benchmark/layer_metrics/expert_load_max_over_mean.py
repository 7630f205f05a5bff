"""Largest over mean load of a held expert in a decode step's layer."""


def read(collected):
    window = collected["engine_window"]
    held = collected["engine_after"]["expert_shape"]["experts_held"]
    if not window["decode_expert_assignments"]:
        return None
    return window["decode_expert_load_max"] * held / window["decode_expert_assignments"]
