"""The tiny cells of the rehearsals: configuration and traffic files under
``cells/`` that no entry of ``workloads`` names, as ``harness.Cell``s."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.dirname(HERE)
# ``row-adagrad``: its driver and reference exist only as files under
# ``cells/``, found there as a later PR's would be under ``paths``.
KINDS = {"dense": ("tiny-dense.json", "tiny-buckets.json"),
         "sparse": ("tiny-sparse.json", "tiny-zipf.json"),
         "row-adagrad": ("tiny-sparse-row-adagrad.json",
                         "tiny-zipf-row-adagrad.json")}


def _json(path):
    with open(path) as fh:
        return json.load(fh)


def cell(kind: str, chips: int = 4):
    import harness

    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    config, traffic = (_json(os.path.join(HERE, "cells", n))
                       for n in KINDS[kind])
    return harness.Cell("tiny-" + kind, chips, config, traffic,
                        bench["end_to_end"], bench["per_layer"],
                        [BENCH, os.path.join(HERE, "cells")])
