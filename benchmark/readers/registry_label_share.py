"""Every label set of a counter of the program's that CONTAINS ``labels``
(``obs.registry.default_registry()``, cumulative over the process), over
the counter's total: a share, in percent. Where ``registry_share`` reads
one exact label set, this one sums over the labels it does not name (a
solver kind over every coordinate)."""

from photon_ml_tpu.obs.registry import default_registry


def read(ctx, counter, labels, scale=100.0):
    series = default_registry().counter(counter).series()
    want = {f"{k}={v}" for k, v in labels.items()}
    total = sum(series.values())
    if total <= 0:
        return None  # the program does not count this: say nothing
    return scale * sum(v for key, v in series.items() if want <= set(key)) / total
