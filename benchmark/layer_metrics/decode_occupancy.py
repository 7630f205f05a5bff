"""Share of the decode slots that held a sequence, over the decode steps of
the window. `stats()` gives `mean_occupancy` = decode tokens over slot-steps
since the engine started; the window's share is the difference of both
counts between the two snapshots."""


def read(collected):
    def slot_steps(stats):
        return stats["decode_tokens"] / stats["mean_occupancy"]

    before, after = collected["engine_before"], collected["engine_after"]
    tokens = after["decode_tokens"] - before["decode_tokens"]
    return 100.0 * tokens / (slot_steps(after) - slot_steps(before))
