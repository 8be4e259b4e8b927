"""Mean of the samples at `metric["samples"]` (dotted path into `out`)."""
from benchmark.harness import stats
from benchmark.harness.spec import dig


def read(evidence, metric):
    samples = dig(evidence["out"], metric["samples"])
    return stats.mean(samples) if samples else None
