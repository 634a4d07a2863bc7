"""Repo tooling: the benchmark-regression gate's pin-preservation
contract and the stale-docs checker."""
import importlib.util
import json
import os
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(rel):
    path = os.path.join(REPO, rel)
    name = os.path.splitext(os.path.basename(rel))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def check_regression():
    return load("benchmarks/check_regression.py")


@pytest.fixture(scope="module")
def docs_check():
    return load("tools/docs_check.py")


# ------------------------------------------------- check_regression pins
def write_json(path, payload):
    with open(path, "w") as f:
        json.dump(payload, f)


def baseline_with_pins(path):
    write_json(path, {
        "kernels": {"attn/blocked_speedup": 4.7, "decode/scan_speedup": 3.0},
        "pins": {"attn/blocked_speedup": 2.0},
        "floors": {"async_rounds/throughput_speedup": 1.5},
    })


def test_update_preserves_pins_and_floors(check_regression, tmp_path):
    baseline = str(tmp_path / "baseline.json")
    results = str(tmp_path / "results.json")
    baseline_with_pins(baseline)
    write_json(results, {"attn": {"ref_us": 10.0, "blocked_us": 2.0},
                         "async_rounds": {"throughput_speedup": 1.7}})
    assert check_regression.main(
        ["--baseline", baseline, "--results", results, "--update"]) == 0
    out = json.load(open(baseline))
    # measured section refreshed...
    assert out["kernels"]["attn/blocked_speedup"] == pytest.approx(5.0)
    assert out["kernels"]["async_rounds/throughput_speedup"] == 1.7
    # ...pins and floors byte-for-byte as committed
    assert out["pins"] == {"attn/blocked_speedup": 2.0}
    assert out["floors"] == {"async_rounds/throughput_speedup": 1.5}


def test_update_pins_refreshes_to_measured(check_regression, tmp_path):
    baseline = str(tmp_path / "baseline.json")
    results = str(tmp_path / "results.json")
    baseline_with_pins(baseline)
    write_json(results, {"attn": {"ref_us": 10.0, "blocked_us": 2.0}})
    assert check_regression.main(
        ["--baseline", baseline, "--results", results,
         "--update", "--update-pins"]) == 0
    out = json.load(open(baseline))
    # the pinned key follows this run's measurement; no new pins appear
    assert out["pins"] == {"attn/blocked_speedup": pytest.approx(5.0)}
    assert out["floors"] == {"async_rounds/throughput_speedup": 1.5}


def test_update_pins_keeps_unmeasured_pin(check_regression, tmp_path):
    baseline = str(tmp_path / "baseline.json")
    results = str(tmp_path / "results.json")
    baseline_with_pins(baseline)
    write_json(results, {"decode": {"ref_us": 9.0, "scan_us": 3.0}})
    assert check_regression.main(
        ["--baseline", baseline, "--results", results,
         "--update", "--update-pins"]) == 0
    out = json.load(open(baseline))
    # nothing measured for the pinned key this run -> prior value survives
    assert out["pins"] == {"attn/blocked_speedup": 2.0}


def test_update_pins_requires_update(check_regression, tmp_path):
    with pytest.raises(SystemExit):
        check_regression.main(["--update-pins"])


def test_pins_overlay_and_floors_gate(check_regression, tmp_path, capsys):
    baseline = str(tmp_path / "baseline.json")
    results = str(tmp_path / "results.json")
    baseline_with_pins(baseline)
    # measured 1.9x vs pinned 2.0 is within the 25% drift gate (the 4.7
    # reference measurement is overlaid by the pin), but the hard floor
    # fails the under-1.5 async speedup verbatim
    write_json(results, {"attn": {"ref_us": 19.0, "blocked_us": 10.0},
                         "async_rounds": {"throughput_speedup": 1.4}})
    rc = check_regression.main(["--baseline", baseline, "--results", results])
    out = capsys.readouterr().out
    assert rc == 1
    assert "ok   attn/blocked_speedup" in out
    assert "HARD floor" in out


# ----------------------------------------------------------- docs_check
def test_docs_check_passes_on_this_repo(docs_check):
    assert docs_check.main() == 0


def mini_repo(root):
    (root / "src" / "repro" / "launch").mkdir(parents=True)
    (root / "src" / "repro" / "launch" / "train.py").write_text(
        'ap.add_argument("--rounds", type=int)\n')
    (root / "src" / "repro" / "fed").mkdir()
    (root / "src" / "repro" / "fed" / "engine.py").write_text("")
    (root / "benchmarks").mkdir()
    (root / "benchmarks" / "run.py").write_text(
        'SUITES = {\n    "async": ("m", "d"),\n}\n'
        'ap.add_argument("--only", action="append")\n')
    (root / "docs").mkdir()


def test_docs_check_flags_every_stale_kind(docs_check, tmp_path,
                                           monkeypatch):
    mini_repo(tmp_path)
    doc = tmp_path / "docs" / "GUIDE.md"
    doc.write_text(textwrap.dedent("""\
        Good: `--rounds`, `repro.fed.engine`, `benchmarks/run.py`,
        `python benchmarks/run.py --only async`, [ok](../benchmarks/run.py).
        Stale flag `--no-such-flag`, stale path `src/gone.py`,
        stale module `repro.fed.missing`,
        stale suite `run.py --only nope`,
        [dead](missing.md).
        """))
    monkeypatch.setattr(docs_check, "ROOT", tmp_path)
    monkeypatch.setattr(docs_check, "CHECKED_DOCS", ("docs/GUIDE.md",))
    assert docs_check.main() == 1
    stale = docs_check.check_doc("docs/GUIDE.md", docs_check.defined_flags(),
                                 docs_check.defined_suites())
    kinds = "\n".join(stale)
    assert "--no-such-flag" in kinds
    assert "src/gone.py" in kinds
    assert "repro.fed.missing" in kinds
    assert "suite `nope`" in kinds
    assert "missing.md" in kinds
    assert len(stale) == 5          # nothing valid was flagged


def test_docs_check_ignores_prose_and_fence_noise(docs_check, tmp_path,
                                                  monkeypatch):
    mini_repo(tmp_path)
    doc = tmp_path / "docs" / "GUIDE.md"
    doc.write_text(textwrap.dedent("""\
        Prose mentioning --not-code or bare.py stays advisory (no
        backticks). Inline math like `alpha / (1 + s)^beta` and ASCII
        art are not references:

        ```
        c0 ██████ --rounds 4
        weights = keep * size
        ```
        """))
    monkeypatch.setattr(docs_check, "ROOT", tmp_path)
    monkeypatch.setattr(docs_check, "CHECKED_DOCS", ("docs/GUIDE.md",))
    assert docs_check.main() == 0


def test_docs_check_fails_on_missing_doc(docs_check, tmp_path, monkeypatch):
    mini_repo(tmp_path)
    monkeypatch.setattr(docs_check, "ROOT", tmp_path)
    monkeypatch.setattr(docs_check, "CHECKED_DOCS", ("docs/ABSENT.md",))
    assert docs_check.main() == 1


def test_docs_check_knows_negated_boolean_flags(docs_check, tmp_path,
                                                monkeypatch):
    mini_repo(tmp_path)
    (tmp_path / "src" / "repro" / "launch" / "serve.py").write_text(
        'ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,'
        '\n                default=True)\n')
    monkeypatch.setattr(docs_check, "ROOT", tmp_path)
    flags = docs_check.defined_flags()
    assert {"--reduced", "--no-reduced", "--rounds"} <= flags
    assert "--no-rounds" not in flags
