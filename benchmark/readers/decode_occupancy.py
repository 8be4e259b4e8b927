"""Share of decode rows that carried a request: tokens delivered over
decode substeps x slots, both differenced over the window. The first token
of a request is sampled by its prefill, not by a decode row, so those are
taken off the tokens."""


def read(evidence, metric):
    c = evidence["out"].get("counters") or {}
    eng = c.get("engine")
    if not eng or not eng.get("decode_steps"):
        return None
    decoded = eng["tokens_out"] - eng["prefills"]
    return 100.0 * decoded / (eng["decode_steps"] * c["slots"])
