"""`shifu-tpu lint` suite: per-rule fixture pairs (a seeded violation
that must flag + a clean twin that must not), suppression-comment and
baseline mechanics, CLI exit codes, and the tier-1 acceptance guards —
the full shifu_tpu/ tree lints clean against the checked-in baseline,
in under 5 seconds, with byte-deterministic output."""

import json
import os
import textwrap
import time

import pytest

from shifu_tpu.lint import run_lint
from shifu_tpu.lint.baseline import (apply_baseline, load_baseline,
                                     write_baseline)
from shifu_tpu.lint.cli import (default_baseline_path, main,
                                repo_root)
from shifu_tpu.lint.engine import Finding, LintEngine, iter_python_files
from shifu_tpu.lint.rules import ALL_RULES, make_rules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.lint           # `pytest -m lint` collects this


def _lint_snippet(tmp_path, source, rules=None, rel="mod.py"):
    """Write one fixture module and lint it; returns findings."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    findings, _ = run_lint([str(path)], rules=rules, root=str(tmp_path),
                           full_tree=False)
    return findings


def _rules_hit(findings):
    return {f.rule for f in findings}


# ------------------------------------------------ rule 1: host-sync
def test_host_sync_flags_and_clean_twin(tmp_path):
    bad = """
        import jax

        @jax.jit
        def f(x):
            return float(x) + 1.0

        @jax.jit
        def g(x):
            return x.sum().item()
    """
    found = _lint_snippet(tmp_path, bad, rules=["host-sync-hot-path"])
    assert len(found) == 2
    assert _rules_hit(found) == {"host-sync-hot-path"}

    clean = """
        import jax
        import numpy as np
        from functools import partial

        @partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            return x * int(np.log2(n))     # static host math: sanctioned

        def host(x):
            return float(x)                # not jitted: fine
    """
    assert _lint_snippet(tmp_path, clean,
                         rules=["host-sync-hot-path"]) == []


def test_host_sync_window_loop(tmp_path):
    bad = """
        def sweep(stream, f):
            tot = 0.0
            for w in stream.prepared(f):
                tot += w.err.item()        # per-window forced fetch
            return tot
    """
    (f,) = _lint_snippet(tmp_path, bad, rules=["host-sync-hot-path"])
    assert "window loop" in f.message

    clean = """
        def sweep(stream, f):
            accs = []
            for w in stream.prepared(f):
                accs.append(w.err)         # accumulate on device
            return [a.item() for a in accs]   # fetch after the sweep
    """
    assert _lint_snippet(tmp_path, clean,
                         rules=["host-sync-hot-path"]) == []


# ------------------------------------------- rule 2: recompile-hazard
def test_recompile_hazard_flags_and_clean_twin(tmp_path):
    bad = """
        import jax

        @jax.jit
        def hot(x):
            return x + 1

        def build():
            return jax.jit(lambda x: x * 2)
    """
    found = _lint_snippet(tmp_path, bad, rules=["recompile-hazard"],
                          rel="train/mod.py")
    assert len(found) == 2
    # the same module OUTSIDE a hot layer is sanctioned (ops/ kernels)
    assert _lint_snippet(tmp_path, bad, rules=["recompile-hazard"],
                         rel="ops/mod.py") == []

    clean = """
        from shifu_tpu import obs

        @obs.costed_jit("plane.hot", lazy=True)
        def hot(x):
            return x + 1
    """
    assert _lint_snippet(tmp_path, clean, rules=["recompile-hazard"],
                         rel="train/mod.py") == []


def test_recompile_hazard_fstring_executable_name(tmp_path):
    bad = """
        from shifu_tpu import obs

        def wrap(fn, shape):
            return obs.costed_jit(f"plane.fn.{shape}", fn)
    """
    (f,) = _lint_snippet(tmp_path, bad, rules=["recompile-hazard"],
                         rel="serve/mod.py")
    assert "f-string executable name" in f.message
    # a CONSTANT f-string (no interpolation) is just a string
    clean = """
        from shifu_tpu import obs

        def wrap(fn):
            return obs.costed_jit(f"plane.fn", fn)
    """
    assert _lint_snippet(tmp_path, clean, rules=["recompile-hazard"],
                         rel="serve/mod.py") == []


# --------------------------------------------- rule 3: knob-registry
def test_knob_registry_flags_and_clean_twin(tmp_path):
    bad = """
        import os
        from shifu_tpu.config import environment

        def f():
            a = environment.get_int("shifu.bogus.knob", 3)
            b = os.environ.get("SHIFU_BOGUS_ENV")
            return a, b

        def g():
            '''Tune with ``-Dshifu.made.up`` if slow.'''
    """
    found = _lint_snippet(tmp_path, bad, rules=["knob-registry"])
    tokens = {m.split("'")[1] for m in (f.message for f in found)}
    assert tokens == {"shifu.bogus.knob", "SHIFU_BOGUS_ENV",
                      "shifu.made.up"}

    clean = """
        import os
        from shifu_tpu.config import environment

        def f():
            '''``-Dshifu.serve.maxDelayMs`` bounds the deadline; a
        line-wrapped mention like ``shifu.tree.`` resolves as a prefix,
        and case-insensitive props (``shifu.train.windowrows``) match.'''
            a = environment.get_float("shifu.serve.maxDelayMs", 2.0)
            b = os.environ.get("SHIFU_TREE_BATCH")
            return a, b
    """
    assert _lint_snippet(tmp_path, clean, rules=["knob-registry"]) == []


def test_knob_registry_readme_and_dead_knob_cross_checks():
    """finish() checks run on full-tree scans: every declared knob is in
    the README table and referenced somewhere in shifu_tpu/ (asserted
    clean on HEAD by the acceptance test; here: the checks exist)."""
    findings, engine = run_lint(rules=["knob-registry"])
    assert engine.full_tree
    assert [f for f in findings
            if "README" in f.message or "never read" in f.message] == []


# ---------------------------------------------- rule 4: atomic-write
def test_atomic_write_flags_and_clean_twins(tmp_path):
    bad = """
        import json
        import numpy as np

        def save(path, doc, arr):
            with open(path, "w") as f:
                json.dump(doc, f)
            np.savez(path + ".npz", arr=arr)
    """
    found = _lint_snippet(tmp_path, bad, rules=["atomic-write"])
    assert len(found) == 2

    clean = """
        import io
        import json
        import os
        import numpy as np
        from shifu_tpu import ioutil

        def save(path, doc, arr):
            ioutil.atomic_write_json(path, doc)        # library path
            buf = io.BytesIO()
            np.savez(buf, arr=arr)                     # buffer, not disk
            ioutil.atomic_write_bytes(path + ".npz", buf.getvalue())

        def manual(path, doc):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:                  # tmp discipline
                json.dump(doc, f)
            os.replace(tmp, path)

        def read(path):
            with open(path) as f:                      # reads are fine
                return f.read()
    """
    assert _lint_snippet(tmp_path, clean, rules=["atomic-write"]) == []


# -------------------------------------------- rule 5: telemetry-guard
def test_telemetry_guard_flags_and_clean_twins(tmp_path):
    bad = """
        from shifu_tpu import obs

        def sweep(windows):
            for w in windows:
                obs.counter("ingest.windows_emitted").inc()
    """
    (f,) = _lint_snippet(tmp_path, bad, rules=["telemetry-guard"])
    assert "hoist" in f.message

    clean = """
        from shifu_tpu import obs

        def hoisted(windows):
            c = obs.counter("ingest.windows_emitted")
            for w in windows:
                c.inc()

        def guarded(windows):
            for w in windows:
                if obs.enabled():
                    obs.counter("ingest.windows_emitted").inc()

        def guarded_hoisted_bool(windows, obs_on):
            for w in windows:
                if obs_on:
                    obs.counter("ingest.windows_emitted").inc()
    """
    assert _lint_snippet(tmp_path, clean,
                         rules=["telemetry-guard"]) == []


# ------------------------------------- rules 6-8: manifest migration
def test_manifest_rules_flag_and_clean_twins(tmp_path):
    bad = """
        from shifu_tpu import obs, faults

        def f():
            obs.counter("ingest.windows_emited").inc()     # typo
            obs.gauge("train.epoch_s").set(1.0)            # wrong type
            with obs.span("serve.requst"):                 # typo
                pass
            with obs.span(f"serve.{f.__name__}"):          # no families
                pass
            faults.fire("norm", "shardz", 1)               # typo
    """
    found = _lint_snippet(tmp_path, bad,
                          rules=["metric-manifest", "span-manifest",
                                 "fault-site"])
    assert sorted(_rules_hit(found)) == ["fault-site", "metric-manifest",
                                         "span-manifest"]
    assert len(found) == 5

    clean = """
        from shifu_tpu import obs, faults

        def f(name):
            obs.counter("ingest.windows_emitted").inc()
            obs.histogram("train.epoch_s").observe(1.0)
            obs.gauge(f"eval.{name}.auc").set(1.0)    # declared prefix
            with obs.span("serve.request"):
                pass
            with obs.span(name):                      # variable: exempt
                pass
            faults.fire("norm", "shard", 1)
    """
    assert _lint_snippet(tmp_path, clean,
                         rules=["metric-manifest", "span-manifest",
                                "fault-site"]) == []


# ------------------------------------------------ suppression comments
def test_inline_and_file_suppressions(tmp_path):
    src = """
        import json

        def a(path, doc):
            with open(path, "w") as f:  # shifu-lint: disable=atomic-write -- why
                json.dump(doc, f)

        def b(path, doc):
            # shifu-lint: disable=atomic-write
            with open(path, "w") as f:
                json.dump(doc, f)

        def c(path, doc):
            with open(path, "w") as f:  # shifu-lint: disable=other-rule
                json.dump(doc, f)
    """
    found = _lint_snippet(tmp_path, src, rules=["atomic-write"])
    assert len(found) == 1              # only c(): wrong rule named
    assert found[0].line == 14

    filewide = """
        # shifu-lint: disable-file=atomic-write
        import json

        def a(path, doc):
            with open(path, "w") as f:
                json.dump(doc, f)

        def b(path, doc):
            with open(path, "w") as f:
                json.dump(doc, f)
    """
    assert _lint_snippet(tmp_path, filewide, rules=["atomic-write"]) == []


# --------------------------------------------------- baseline mechanics
def _f(rule="atomic-write", path="p.py", line=1, msg="m"):
    return Finding(path, line, 0, rule, msg)


def test_baseline_roundtrip_and_apply(tmp_path):
    bl = str(tmp_path / "bl.json")
    write_baseline(bl, [_f(line=1), _f(line=9), _f(msg="other")])
    loaded = load_baseline(bl)
    assert loaded[("atomic-write", "p.py", "m")] == 2   # count-merged
    assert loaded[("atomic-write", "p.py", "other")] == 1

    # 3 current findings with the same fingerprint vs a budget of 2:
    # the extra one is NEW; a baselined fingerprint with no current
    # finding is STALE
    current = [_f(line=1), _f(line=2), _f(line=3)]
    new, old, stale = apply_baseline(current, loaded)
    assert [f.line for f in old] == [1, 2]
    assert [f.line for f in new] == [3]
    assert stale == [("atomic-write", "p.py", "other")]

    # line moves do NOT churn the baseline (fingerprint drops the line)
    new, old, stale = apply_baseline(
        [_f(line=77), _f(line=78), _f(msg="other")], loaded)
    assert new == [] and stale == []

    # the ratchet: fixing SOME of a fingerprint's occurrences leaves
    # unused budget, which reports stale — the baseline must shrink
    new, old, stale = apply_baseline([_f(line=77), _f(msg="other")],
                                     loaded)
    assert new == [] and stale == [("atomic-write", "p.py", "m")]


def test_baseline_missing_and_bad_version(tmp_path):
    assert load_baseline(str(tmp_path / "nope.json")) == {}
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 99, "findings": []}))
    with pytest.raises(ValueError):
        load_baseline(str(bad))


# ----------------------------------------------------------- engine / CLI
def test_parse_error_is_a_finding(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    findings, _ = run_lint([str(tmp_path / "broken.py")],
                           root=str(tmp_path), full_tree=False)
    assert [f.rule for f in findings] == ["parse-error"]


def test_iter_python_files_sorted_deduped(tmp_path):
    (tmp_path / "b.py").write_text("")
    (tmp_path / "a.py").write_text("")
    (tmp_path / "sub").mkdir()
    (tmp_path / "sub" / "c.py").write_text("")
    (tmp_path / "__pycache__").mkdir()
    (tmp_path / "__pycache__" / "x.py").write_text("")
    got = list(iter_python_files([str(tmp_path), str(tmp_path / "a.py")]))
    names = [os.path.relpath(p, tmp_path) for p in got]
    assert names == ["a.py", "b.py", os.path.join("sub", "c.py")]


def test_unknown_rule_name_raises():
    with pytest.raises(ValueError, match="unknown rule"):
        make_rules(["no-such-rule"])


def test_cli_exit_codes_and_json(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import json\n"
                   "def a(p, d):\n"
                   "    with open(p, 'w') as f:\n"
                   "        json.dump(d, f)\n")
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")

    assert main([str(clean), "--no-baseline"]) == 0
    capsys.readouterr()
    assert main([str(bad), "--no-baseline"]) == 2
    out = capsys.readouterr().out
    assert "atomic-write" in out and "bad.py" in out

    assert main([str(bad), "--no-baseline", "--json"]) == 2
    doc = json.loads(capsys.readouterr().out)
    (f,) = doc["new"]
    assert f["rule"] == "atomic-write" and f["line"] == 3
    assert doc["files_scanned"] == 1

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for cls in ALL_RULES:
        assert cls.name in out

    assert main([str(bad), "--rules", "nope"]) == 1


def test_cli_baseline_workflow(tmp_path, capsys):
    """--update-baseline grandfathers today's debt; the next run is
    clean; FIXING the debt turns the entry stale (exit 2) so the
    baseline cannot rot."""
    bad = tmp_path / "bad.py"
    bad.write_text("import json\n"
                   "def a(p, d):\n"
                   "    with open(p, 'w') as f:\n"
                   "        json.dump(d, f)\n")
    bl = str(tmp_path / "bl.json")
    assert main([str(bad), "--baseline", bl]) == 2
    capsys.readouterr()
    assert main([str(bad), "--baseline", bl, "--update-baseline"]) == 0
    capsys.readouterr()
    assert main([str(bad), "--baseline", bl]) == 0
    assert "grandfathered" in capsys.readouterr().out
    bad.write_text("x = 1\n")
    assert main([str(bad), "--baseline", bl]) == 2
    assert "stale baseline entry" in capsys.readouterr().out


def test_cli_subcommand_dispatch(capsys):
    """`shifu-tpu lint` is wired through the main CLI dispatcher."""
    from shifu_tpu.cli import main as cli_main
    assert cli_main(["lint", "--list-rules"]) == 0
    assert "knob-registry" in capsys.readouterr().out


# ----------------------------------------------------- tier-1 acceptance
def test_full_tree_lints_clean_against_checked_in_baseline():
    """ACCEPTANCE: `shifu-tpu lint` exits 0 on HEAD — every knob
    resolves against config/knobs.py, every write/metric/span/fault
    literal honors its contract, and the checked-in baseline is EMPTY
    (no grandfathered debt survived this round)."""
    findings, engine = run_lint()
    assert engine.files_scanned > 60
    baseline = load_baseline(default_baseline_path())
    assert baseline == {}               # nothing was cheap-to-fix left
    new, _, stale = apply_baseline(findings, baseline)
    assert not new, "\n".join(f.render() for f in new)
    assert not stale


def test_full_tree_fast_and_byte_deterministic():
    """ACCEPTANCE: a full-tree run completes in < 5 s and two runs
    render byte-identically (stable file order, stable finding order —
    CI can diff outputs)."""
    t0 = time.perf_counter()
    f1, _ = run_lint()
    elapsed = time.perf_counter() - t0
    assert elapsed < 5.0, f"full-tree lint took {elapsed:.2f}s"
    f2, _ = run_lint()
    r1 = b"\n".join(f.render().encode() for f in f1)
    r2 = b"\n".join(f.render().encode() for f in f2)
    assert r1 == r2


def test_every_rule_has_name_doc_and_fires_somewhere():
    """Catalogue hygiene: unique names, non-empty docs, and every rule
    has at least one seeded-violation test above (checked by name)."""
    names = [cls.name for cls in ALL_RULES]
    assert len(names) == len(set(names))
    for cls in ALL_RULES:
        assert cls.name and cls.doc
    here = open(__file__).read()
    for cls in ALL_RULES:
        assert cls.name in here, f"no fixture exercises {cls.name}"
