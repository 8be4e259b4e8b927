"""A number the run reported under `metric["field"]` (dotted path)."""
from benchmark.harness.spec import dig


def read(evidence, metric):
    return dig(evidence["out"], metric["field"])
