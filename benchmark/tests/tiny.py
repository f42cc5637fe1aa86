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


def cell(kind: str, chips: int = 4, root: str = ROOT):
    """``root``: where ``BENCHMARK.json`` lies (its metrics, and its
    ``paths`` beside the rehearsals' own directory)."""
    import harness

    bench = _json(os.path.join(root, "BENCHMARK.json"))
    config, traffic = (_json(os.path.join(HERE, "cells", n))
                       for n in KINDS[kind])
    return harness.Cell("tiny-" + kind, chips, config, traffic,
                        bench["end_to_end"], bench["per_layer"],
                        harness.search_dirs(root)
                        + [os.path.join(HERE, "cells")])


def check_metrics(result: dict, group: str, at_least: set,
                  root: str = ROOT) -> set:
    """The names of the run's metrics, held to what every addition leaves
    true: ``at_least`` are there, every one is an entry of ``group``
    (``end_to_end`` or ``per_layer``) of ``BENCHMARK.json`` with its unit,
    and none comes from a device's trace, which a CPU run has not.  A later
    PR may append an entry that a CPU run can read (a counter, a span of
    the program's): no test here counts the entries or asks where one
    stands in its list."""
    entries = {m["name"]: m
               for m in _json(os.path.join(root, "BENCHMARK.json"))[group]}
    got = set(result["metrics"])
    assert at_least <= got <= set(entries), (at_least - got,
                                             got - set(entries))
    for name in got:
        assert result["metrics"][name]["unit"] == entries[name]["unit"]
        assert group == "end_to_end" \
            or entries[name]["source"] != "device_trace", name
    return got
