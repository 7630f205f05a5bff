"""How many times less cache the running sequences held than one class for
every attention layer would: every layer at the full class's tokens over
the full layers at theirs and the window layers at theirs (`stats()`
`held_tokens_full` / `held_tokens_window`, window differences; layers from
`cache_classes`)."""


def read(collected):
    window = collected["engine_window"]
    classes = collected["engine_after"]["cache_classes"]
    full, win = classes["full"]["layers"], classes["window"]["layers"]
    held = full * window["held_tokens_full"] + win * window["held_tokens_window"]
    if not held:
        return None
    return (full + win) * window["held_tokens_full"] / held
