"""graftlint tier-1 tests.

Covers: every rule firing on its fixture and staying quiet on the
clean twin, the interprocedural (semantic-index) layer firing on
cross-function shapes the single-pass engine provably misses,
suppression comments, the baseline round-trip, the index cache, and —
the gate that matters — a clean full-package run: ``ray_tpu/`` must
have zero non-baselined findings (and this repo's committed baseline
is empty, so zero findings, full stop), for a counted amount of work.
"""

import json
import os

import pytest

from ray_tpu.devtools import baseline as baseline_mod
from ray_tpu.devtools.driver import lint_paths, lint_source
from ray_tpu.devtools.lint import default_baseline_path, main, repo_root
from ray_tpu.devtools.registry import (all_index_rules, all_rules,
                                       index_rule_catalog, rule_catalog)
from ray_tpu.devtools.semindex import build_index

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")


def lint_fixture(name, index_rules=None):
    # index_cache="" keeps fixture runs hermetic (no shared temp cache)
    return lint_paths([os.path.join(FIXTURES, name)], all_rules(),
                      root=FIXTURES, index_rules=index_rules,
                      index_cache="")


# -------------------------------------------------------------- rule cases

RULE_CASES = [
    ("GL001", "async-blocking", "gl001_fire.py", "gl001_ok.py", 3),
    ("GL002", "discarded-future", "gl002_fire.py", "gl002_ok.py", 2),
    ("GL003", "spmd-nondeterminism", "gl003_fire.py", "gl003_ok.py", 3),
    ("GL004", "host-transfer", "gl004_fire.py", "gl004_ok.py", 3),
    ("GL005", "guarded-by", "gl005_fire.py", "gl005_ok.py", 3),
    ("GL006", "except-hygiene", "gl006_fire.py", "gl006_ok.py", 3),
    ("GL007", "unreleased-store-ref", "gl007_fire.py", "gl007_ok.py", 3),
    ("GL008", "oneway-return", "gl008_fire.py", "gl008_ok.py", 4),
    ("GL009", "lock-order", "gl009_fire.py", "gl009_ok.py", 3),
    ("GL010", "global-guarded-by", "gl010_fire.py", "gl010_ok.py", 3),
    ("GL011", "oneway-exception", "gl011_fire.py", "gl011_ok.py", 4),
    ("GL012", "blocking-under-lock", "gl012_fire.py", "gl012_ok.py", 3),
    ("GL013", "handler-reentry", "gl013_fire.py", "gl013_ok.py", 3),
    ("GL014", "sequential-rpc-in-loop", "gl014_fire.py", "gl014_ok.py", 3),
    ("GL015", "wallclock-duration", "gl015_fire.py", "gl015_ok.py", 3),
    ("GL016", "bare-print", "gl016_fire.py", "gl016_ok.py", 3),
    ("GL018", "unbounded-accumulator", "gl018_fire.py", "gl018_ok.py", 3),
    ("GL019", "host-sync-in-step-loop", "gl019_fire.py", "gl019_ok.py", 4),
]


@pytest.mark.parametrize("code,name,fire,ok,n_expected", RULE_CASES,
                         ids=[c[0] for c in RULE_CASES])
def test_rule_fires_and_stays_quiet(code, name, fire, ok, n_expected):
    firing = lint_fixture(fire)
    assert [f.code for f in firing] == [code] * n_expected, (
        f"{fire}: expected {n_expected} {code} findings, got "
        f"{[(f.code, f.line, f.message) for f in firing]}")
    assert all(f.rule == name for f in firing)
    clean = lint_fixture(ok)
    assert clean == [], (
        f"{ok} should be clean, got "
        f"{[(f.code, f.line, f.message) for f in clean]}")


def test_rule_catalog_complete():
    catalog = rule_catalog()
    assert [c.code for c in catalog] == [
        "GL001", "GL002", "GL003", "GL004", "GL005", "GL006", "GL007",
        "GL008", "GL009", "GL010", "GL011", "GL012", "GL013", "GL014",
        "GL015", "GL016", "GL018", "GL019"]
    for cls in catalog:
        assert cls.name and cls.description and cls.invariant
    index_catalog = index_rule_catalog()
    assert [c.selector() for c in index_catalog] == [
        "GL009.inter", "GL012.inter", "GL013.inter", "GL017"]
    for cls in index_catalog:
        assert cls.name and cls.description and cls.invariant


def test_select_filters_rules():
    findings = lint_paths([os.path.join(FIXTURES, "gl006_fire.py")],
                          all_rules({"GL002"}), root=FIXTURES,
                          index_cache="")
    assert findings == []  # only the discarded-future rule ran


# ------------------------------------------- the indexed (v2) layer

# (code, fire fixture, ok fixture, expected finding count). Every fire
# fixture is a shape the pre-v2 single-pass engine PROVABLY misses —
# asserted below by running it with the indexed layer disabled.
INTER_CASES = [
    ("GL012", "gl012_inter_fire.py", "gl012_inter_ok.py", 2),
    ("GL013", "gl013_inter_fire.py", "gl013_inter_ok.py", 3),
    ("GL009", "gl009_inter_fire.py", "gl009_inter_ok.py", 2),
    ("GL012", "effects_override_fire.py", "effects_override_ok.py", 1),
    ("GL017", "gl017_fire.py", "gl017_ok.py", 2),
]


@pytest.mark.parametrize("code,fire,ok,n_expected", INTER_CASES,
                         ids=[c[1][:-3] for c in INTER_CASES])
def test_interprocedural_fires_and_stays_quiet(code, fire, ok,
                                               n_expected):
    firing = lint_fixture(fire)
    assert [f.code for f in firing] == [code] * n_expected, (
        f"{fire}: expected {n_expected} {code} findings, got "
        f"{[(f.code, f.line, f.message) for f in firing]}")
    if code != "GL017":  # GL017 needs no chain: the annotation IS the site
        assert all(f.chain for f in firing), "indexed finding lost its chain"
    clean = lint_fixture(ok)
    assert clean == [], (
        f"{ok} should be clean, got "
        f"{[(f.code, f.line, f.message) for f in clean]}")


@pytest.mark.parametrize("code,fire,ok,n_expected", INTER_CASES,
                         ids=[c[1][:-3] for c in INTER_CASES])
def test_single_pass_engine_misses_inter_fixture(code, fire, ok,
                                                 n_expected):
    """The point of the index: the per-file engine alone (index_rules
    disabled — exactly the pre-v2 behavior) sees nothing here."""
    assert lint_fixture(fire, index_rules=[]) == []


def test_effects_annotation_freezes_inference():
    """The ok twin only differs from firing by its '# effects: none'
    line — inference would flag the statically-blocking callee."""
    src = open(os.path.join(FIXTURES, "effects_override_ok.py")).read()
    assert "# effects: none" in src
    assert lint_fixture("effects_override_ok.py") == []


def test_chain_excluded_from_fingerprint():
    f1, f2 = lint_fixture("gl012_inter_fire.py")
    bare = type(f1)(path=f1.path, line=f1.line, col=f1.col,
                    rule=f1.rule, code=f1.code, message=f1.message,
                    line_text=f1.line_text, occurrence=f1.occurrence)
    assert f1.chain and bare.fingerprint() == f1.fingerprint()


def test_select_inter_sublayer_only():
    """GL012.inter selects only the indexed layer; plain GL012 both."""
    inter_only = lint_paths(
        [os.path.join(FIXTURES, "gl012_fire.py")],
        all_rules({"GL012.inter"}), root=FIXTURES,
        index_rules=all_index_rules({"GL012.inter"}), index_cache="")
    assert inter_only == []  # per-file shapes: inter layer is quiet
    both = lint_paths(
        [os.path.join(FIXTURES, "gl012_inter_fire.py")],
        all_rules({"GL012"}), root=FIXTURES,
        index_rules=all_index_rules({"GL012"}), index_cache="")
    assert [f.code for f in both] == ["GL012", "GL012"]
    with pytest.raises(ValueError):
        all_index_rules({"GL099.inter"})


def test_suppression_covers_indexed_layer(tmp_path):
    src = open(os.path.join(FIXTURES, "gl012_inter_fire.py")).read()
    src = src.replace(
        "self._table[key] = self._read_disk(path)  # GL012.inter",
        "self._table[key] = self._read_disk(path)  "
        "# graftlint: disable=blocking-under-lock")
    src = src.replace(
        "self._nap()  # GL012.inter",
        "self._nap()  # graftlint: disable=GL012")
    p = tmp_path / "suppressed_inter.py"
    p.write_text(src)
    findings = lint_paths([str(p)], all_rules(), root=str(tmp_path),
                          index_cache="")
    assert findings == []


# ------------------------------------------------------ index cache

def test_index_cache_invalidation(tmp_path):
    a, b = tmp_path / "a.py", tmp_path / "b.py"
    a.write_text("def f():\n    return 1\n")
    b.write_text("def g():\n    return 2\n")
    cache = str(tmp_path / "cache.json")
    paths, root = [str(a), str(b)], str(tmp_path)

    idx = build_index(paths, root, cache_path=cache)
    assert sorted(idx.stats.extracted) == ["a.py", "b.py"]
    # clean re-run: everything served from the content-hash cache
    idx = build_index(paths, root, cache_path=cache)
    assert idx.stats.extracted == []
    assert sorted(idx.stats.cached) == ["a.py", "b.py"]
    # edit one file: only it re-extracts
    a.write_text("def f():\n    return 3\n")
    idx = build_index(paths, root, cache_path=cache)
    assert idx.stats.extracted == ["a.py"]
    assert idx.stats.cached == ["b.py"]


def test_index_cache_warm_run_same_findings(tmp_path):
    cache = str(tmp_path / "cache.json")
    fixture = os.path.join(FIXTURES, "gl009_inter_fire.py")
    cold = lint_paths([fixture], all_rules(), root=FIXTURES,
                      index_cache=cache)
    warm = lint_paths([fixture], all_rules(), root=FIXTURES,
                      index_cache=cache)
    assert [f.to_dict() for f in warm] == [f.to_dict() for f in cold]
    assert len(cold) == 2


# ------------------------------------------------------------ suppressions

def test_suppression_comments():
    assert lint_fixture("suppressed.py") == []


def test_suppression_file_level():
    src = ("# graftlint: disable-file=discarded-future\n"
           "def kick(f):\n"
           "    f.remote(1)\n")
    assert lint_source(src, "x.py", all_rules()) == []


def test_unsuppressed_twin_still_fires():
    src = "def kick(f):\n    f.remote(1)\n"
    findings = lint_source(src, "x.py", all_rules())
    assert [f.code for f in findings] == ["GL002"]


# ---------------------------------------------------------------- baseline

def test_baseline_round_trip(tmp_path):
    path = str(tmp_path / "baseline.json")
    findings = lint_fixture("gl002_fire.py")
    assert findings
    baseline_mod.save(path, findings)

    known = baseline_mod.load(path)
    assert len(known) == len(findings)
    new, baselined = baseline_mod.split(lint_fixture("gl002_fire.py"), known)
    assert new == [] and len(baselined) == len(findings)

    # a NEW violation is not absorbed by the baseline
    extra = lint_source("def go(f):\n    f.remote()\n", "new_file.py",
                        all_rules())
    new2, _ = baseline_mod.split(extra, known)
    assert [f.code for f in new2] == ["GL002"]


def test_baseline_fingerprint_survives_line_moves():
    src1 = "def kick(f):\n    f.remote(1)\n"
    src2 = "import os\n\n\ndef kick(f):\n    f.remote(1)\n"
    fp1 = lint_source(src1, "x.py", all_rules())[0].fingerprint()
    fp2 = lint_source(src2, "x.py", all_rules())[0].fingerprint()
    assert fp1 == fp2


def test_baseline_prune(tmp_path):
    path = str(tmp_path / "baseline.json")
    baseline_mod.save(path, lint_fixture("gl002_fire.py"))
    removed = baseline_mod.prune(path, [])  # everything got fixed
    assert removed == 2
    assert baseline_mod.load(path) == {}


# ------------------------------------------------------------------- CLI

def test_cli_list_rules(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for code in ("GL001", "GL006", "GL012.inter", "GL013.inter",
                 "GL009.inter", "GL017"):
        assert code in out


def test_cli_explain_prints_chain(capsys):
    rc = main([os.path.join(FIXTURES, "gl012_inter_fire.py"),
               "--no-baseline", "--explain"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "    | " in out
    assert "blocks: time.sleep" in out


def test_cli_json_chain_field(capsys):
    rc = main([os.path.join(FIXTURES, "gl013_inter_fire.py"),
               "--no-baseline", "--json"])
    assert rc == 1
    data = json.loads(capsys.readouterr().out)
    assert len(data["new"]) == 3
    assert all(f["chain"] for f in data["new"])
    rc = main([os.path.join(FIXTURES, "gl002_fire.py"),
               "--no-baseline", "--json"])
    assert rc == 1
    data = json.loads(capsys.readouterr().out)
    assert all(f["chain"] == [] for f in data["new"])  # per-file layer


def test_cli_json_output(capsys):
    rc = main([os.path.join(FIXTURES, "gl002_fire.py"), "--no-baseline",
               "--json"])
    assert rc == 1
    data = json.loads(capsys.readouterr().out)
    assert len(data["new"]) == 2
    assert data["baselined"] == []
    assert all(f["code"] == "GL002" for f in data["new"])


def test_cli_bad_path():
    assert main(["/nonexistent/nowhere.py"]) == 2


# ------------------------------------------------- the gate: clean package

@pytest.fixture(scope="module")
def package_lint():
    """One lint of ray_tpu/ and what it cost, in work a process can count
    whatever else its machine is doing: parses a file, index builds."""
    import ast
    import collections

    from ray_tpu.devtools import semindex

    parses, builds = collections.Counter(), []
    parse, build = ast.parse, semindex.build_index

    def counted_parse(source, filename="<unknown>", *a, **kw):
        parses[filename] += 1
        return parse(source, filename, *a, **kw)

    def counted_build(*a, **kw):
        builds.append(a)
        return build(*a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ast, "parse", counted_parse)
        mp.setattr(semindex, "build_index", counted_build)
        pkg = os.path.join(repo_root(), "ray_tpu")
        findings = lint_paths([pkg], all_rules(), root=repo_root())
    return findings, parses, len(builds)


def test_package_is_lint_clean_tier1(package_lint):
    """ray_tpu/ has zero non-baselined findings.

    This is the PR gate the devtools exist for: new concurrency/SPMD
    violations fail here before they reach the runtime hot paths.
    """
    findings, _, _ = package_lint
    known = baseline_mod.load(default_baseline_path())
    new, _ = baseline_mod.split(findings, known)
    assert new == [], "new graftlint findings:\n" + "\n".join(
        f.render() for f in new)


def test_package_lint_reads_each_file_once_a_layer(package_lint):
    """The pre-commit viability bar from the devtools charter, held as
    work and not as seconds (which six workers sharing a machine cannot
    promise): a full-package lint parses a file once for the per-file
    rules and at most once more for the index (never, where the cache
    has it), and builds one index."""
    from ray_tpu.devtools.driver import iter_python_files

    _, parses, builds = package_lint
    root = repo_root()
    files = [os.path.relpath(p, root) for p in
             iter_python_files([os.path.join(root, "ray_tpu")])]
    assert len(files) > 100 and builds == 1
    assert {f: parses[f] for f in files if not 1 <= parses[f] <= 2} == {}


def test_committed_baseline_is_empty():
    """Burn-down complete: keep it that way (fix, don't baseline)."""
    assert baseline_mod.load(default_baseline_path()) == {}
