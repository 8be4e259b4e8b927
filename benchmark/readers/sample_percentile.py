"""Percentile `metric["q"]` of the samples at `metric["samples"]`."""
from benchmark.harness import stats
from benchmark.harness.spec import dig


def read(evidence, metric):
    samples = dig(evidence["out"], metric["samples"])
    if not samples:
        return None
    return stats.percentile(samples, metric["q"])
