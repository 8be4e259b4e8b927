"""`memory_analysis()` arguments + temporaries of the cell's largest
program, GB per device."""


def read(evidence, metric):
    out = evidence["out"]
    src = out if "program_argument_bytes" in out else out.get("warm") or {}
    if "program_argument_bytes" not in src:
        return None
    return (src["program_argument_bytes"] + src["program_temp_bytes"]) / 1e9
