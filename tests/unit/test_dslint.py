"""dslint (ISSUE 4, grown cross-module in ISSUE 19): the DSTPU-specific
repo linter (tools/dslint/ package, bin/dstpu_lint) — rule unit tests on
synthetic trees plus the tier-1 enforcement point: the real repo must
lint clean, including the docs/CONFIG.md env-knob table (DSL004/DSL005
knob drift), the serving-layer lock discipline (DSL007) and the
collective-site budgets in deepspeed_tpu/analysis/budgets.py
(DSL008)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "tools"))

import dslint  # noqa: E402


def _write(root, rel, text):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text))
    return path


@pytest.fixture(scope="module")
def repo_knob_reads():
    """One AST scan of the real repo's DSTPU_* read sites, shared by
    every knob-drift assertion below (the scan parses the whole
    operator-settable surface — do it once)."""
    return dslint.scan_env_knobs(REPO)


class TestRepoClean:
    """The enforcement point: every future PR runs this in tier-1."""

    def test_deepspeed_tpu_lints_clean(self, monkeypatch):
        # the repo must lint clean AND the lint must be ONE AST pass:
        # spy on ast.parse for the duration — no file parsed twice no
        # matter how many rules (per-file, knob/metric drift, DSL007
        # locks, DSL008 budgets) consume it
        import ast
        calls = {}
        real_parse = ast.parse

        def spy(src, *a, **kw):
            fn = kw.get("filename", a[0] if a else "<unknown>")
            calls[fn] = calls.get(fn, 0) + 1
            return real_parse(src, *a, **kw)

        monkeypatch.setattr(ast, "parse", spy)
        findings = dslint.lint(["deepspeed_tpu"], repo_root=REPO)
        assert findings == [], "\n".join(str(f) for f in findings)
        dupes = {f: n for f, n in calls.items() if n > 1}
        assert not dupes, f"files parsed more than once: {dupes}"

    def test_config_md_knob_table_current(self, repo_knob_reads):
        # DSL004/DSL005 both directions: the generated env-knob table in
        # docs/CONFIG.md matches the scanned DSTPU_* read sites exactly
        with open(os.path.join(REPO, "docs", "CONFIG.md")) as f:
            documented = {k for k, _ in dslint.documented_knobs(f.read())}
        read = {r.name for r in repo_knob_reads}
        assert documented == read, (
            f"docs/CONFIG.md knob table drifted — run "
            f"tools/gen_config_doc.py (undocumented: "
            f"{sorted(read - documented)}, stale: "
            f"{sorted(documented - read)})")

    def test_knob_scan_finds_known_knobs(self, repo_knob_reads):
        names = {r.name for r in repo_knob_reads}
        # spot-check knobs of three different subsystems
        assert "DSTPU_SERVE_JOURNAL" in names
        assert "DSTPU_FAULT_SITE" in names
        assert "DSTPU_LOADGEN_RATE" in names
        assert len(names) >= 60


class TestCLI:
    def test_exit_zero_and_clean_on_repo(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "dstpu_lint"),
             "deepspeed_tpu"], capture_output=True, text=True, cwd=REPO)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "clean" in proc.stdout

    def test_exit_nonzero_with_rule_id_file_line_format(self, tmp_path):
        bad = _write(str(tmp_path), "deepspeed_tpu/inference/v2/x.py", """
            import jax
            f = jax.jit(lambda x: x)
        """)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "dstpu_lint"),
             bad, "--no-knob-rules", "--root", str(tmp_path)],
            capture_output=True, text=True, cwd=REPO)
        assert proc.returncode == 1
        # `rule-id file:line message` findings format
        first = proc.stdout.splitlines()[0]
        assert first.startswith("DSL002 ")
        assert ":3 " in first

    def test_list_rules(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "dstpu_lint"),
             "--list-rules"], capture_output=True, text=True, cwd=REPO)
        assert proc.returncode == 0
        for rid in ("DSL001", "DSL002", "DSL003", "DSL004", "DSL005"):
            assert rid in proc.stdout


class TestHostSyncRule:
    HOT = {"hot.py": ("plan", "_build")}

    def _lint(self, root):
        return dslint.lint(["hot.py"], repo_root=root,
                           hot_paths=self.HOT, knob_rules=False)

    def test_flags_all_sync_forms_in_hot_path_only(self, tmp_path):
        _write(str(tmp_path), "hot.py", """
            import numpy as np
            import jax
            import jax.numpy as jnp

            def plan(x, res):
                a = np.asarray(res)              # DSL001
                b = res.block_until_ready()      # DSL001
                c = jax.device_get(res)          # DSL001
                d = int(res[0])                  # DSL001 (scalar coerce)
                e = res.item()                   # DSL001
                ok = jnp.asarray(x)              # host->device: fine
                n = int("7")                     # literal: fine
                return a, b, c, d, e, ok, n

            def commit(res):
                return np.asarray(res)           # not registered: fine
        """)
        findings = self._lint(str(tmp_path))
        assert [f.rule for f in findings] == ["DSL001"] * 5
        assert all("plan" in f.message for f in findings)

    def test_nested_defs_covered(self, tmp_path):
        _write(str(tmp_path), "hot.py", """
            import numpy as np

            def _build(self):
                def inner(res):
                    return np.asarray(res)
                return inner
        """)
        assert [f.rule for f in self._lint(str(tmp_path))] == ["DSL001"]

    def test_allow_comment_on_any_statement_line(self, tmp_path):
        # the suppression contract: an allow-comment on ANY line of the
        # flagged (multi-line) call works, not just the first
        _write(str(tmp_path), "hot.py", """
            import numpy as np

            def plan(res):
                return np.asarray(
                    res)  # dslint: allow(DSL001): commit-side readback
        """)
        assert self._lint(str(tmp_path)) == []


class TestDonationRule:
    def _lint(self, root):
        return dslint.lint(["deepspeed_tpu/inference/v2"], repo_root=root,
                           knob_rules=False)

    def test_flags_undonated_jit_only_in_v2(self, tmp_path):
        _write(str(tmp_path), "deepspeed_tpu/inference/v2/r.py", """
            import jax
            good = jax.jit(lambda kv: kv, donate_argnums=(0,))
            named = jax.jit(lambda kv: kv, donate_argnames=("kv",))
            empty = jax.jit(lambda kv: kv, donate_argnums=())  # explicit
            bad = jax.jit(lambda kv: kv)
        """)
        _write(str(tmp_path), "deepspeed_tpu/runtime/t.py", """
            import jax
            outside_v2 = jax.jit(lambda x: x)
        """)
        findings = dslint.lint(["deepspeed_tpu"], repo_root=str(tmp_path),
                               knob_rules=False)
        assert len(findings) == 1
        assert findings[0].rule == "DSL002"
        assert findings[0].line == 6

    def test_allow_comment_suppresses_with_justification(self, tmp_path):
        _write(str(tmp_path), "deepspeed_tpu/inference/v2/r.py", """
            import jax
            # dslint: allow(DSL002): pool is read-only inside the scan
            a = jax.jit(lambda kv: kv)
            b = jax.jit(  # dslint: allow(DSL002): result cached
                lambda kv: kv)
            c = jax.jit(lambda kv: kv)   # unjustified -> flagged
        """)
        findings = self._lint(str(tmp_path))
        assert [(f.rule, f.line) for f in findings] == [("DSL002", 7)]


class TestShardMapImportRule:
    def test_flags_every_import_form_everywhere(self, tmp_path):
        _write(str(tmp_path), "deepspeed_tpu/a.py", """
            from jax.experimental.shard_map import shard_map
        """)
        _write(str(tmp_path), "deepspeed_tpu/b.py", """
            import jax.experimental.shard_map as sm
        """)
        _write(str(tmp_path), "deepspeed_tpu/c.py", """
            from jax.experimental import shard_map
        """)
        _write(str(tmp_path), "deepspeed_tpu/utils/jax_compat.py", """
            from jax.experimental.shard_map import shard_map as _legacy
        """)
        _write(str(tmp_path), "deepspeed_tpu/ok.py", """
            from deepspeed_tpu.utils.jax_compat import shard_map
        """)
        findings = dslint.lint(["deepspeed_tpu"], repo_root=str(tmp_path),
                               knob_rules=False)
        assert sorted(f.path for f in findings) == [
            "deepspeed_tpu/a.py", "deepspeed_tpu/b.py",
            "deepspeed_tpu/c.py", "deepspeed_tpu/utils/jax_compat.py"]
        assert {f.rule for f in findings} == {"DSL003"}


class TestKnobDriftRules:
    def _root(self, tmp_path, code, doc_rows):
        _write(str(tmp_path), "deepspeed_tpu/m.py", code)
        _write(str(tmp_path), "docs/CONFIG.md",
               "# cfg\n\n## Environment knobs (`DSTPU_*`)\n\n"
               "| knob | default | read at |\n|---|---|---|\n"
               + "".join(f"| `{k}` | — | `x` |\n" for k in doc_rows))
        return str(tmp_path)

    def test_undocumented_knob_flagged_at_read_site(self, tmp_path):
        root = self._root(tmp_path, """
            import os
            d = os.environ.get("DSTPU_NEW_KNOB", "1")
        """, ["DSTPU_DOCUMENTED"])
        findings = dslint.lint([], repo_root=root)
        assert ("DSL004", "deepspeed_tpu/m.py") in \
            [(f.rule, f.path) for f in findings]
        assert any("DSTPU_NEW_KNOB" in f.message for f in findings)
        # the documented-but-unread knob is the mirror finding
        assert any(f.rule == "DSL005" and "DSTPU_DOCUMENTED" in f.message
                   for f in findings)

    def test_all_read_idioms_covered(self, tmp_path):
        root = self._root(tmp_path, """
            import os
            import os as _os
            a = os.environ.get("DSTPU_A")
            b = os.environ["DSTPU_B"]
            c = os.getenv("DSTPU_C", "x")
            d = os.environ.pop("DSTPU_D", "")
            e = "DSTPU_E" in os.environ
            f = _os.environ.get("DSTPU_F")
        """, ["DSTPU_A", "DSTPU_B", "DSTPU_C", "DSTPU_D", "DSTPU_E",
              "DSTPU_F"])
        assert dslint.lint([], repo_root=root) == []
        names = {r.name for r in dslint.scan_env_knobs(root)}
        assert names == {"DSTPU_A", "DSTPU_B", "DSTPU_C", "DSTPU_D",
                         "DSTPU_E", "DSTPU_F"}

    def test_defaults_recorded(self, tmp_path):
        root = self._root(tmp_path, """
            import os
            c = os.environ.get("DSTPU_C", "256")
            b = os.environ["DSTPU_B"]
            d = os.environ.get("DSTPU_D", str(4 + 4))
        """, ["DSTPU_B", "DSTPU_C", "DSTPU_D"])
        reads = {r.name: r.default for r in dslint.scan_env_knobs(root)}
        # literal default kept verbatim; computed default is "(dynamic)"
        # (NOT None — only a truly default-less read documents as
        # required); no-default subscript is None
        assert reads == {"DSTPU_C": "'256'", "DSTPU_B": None,
                         "DSTPU_D": "(dynamic)"}


class TestLockDisciplineRule:
    """DSL007 golden fixtures — synthetic thread-root registries over
    tmp trees (the real serving-layer registry is enforced by
    TestRepoClean)."""

    ROOTS = {"race.py": {"Pool": {"put": "admit", "drain": "absorb"}}}

    def _lint(self, root, roots=None):
        return dslint.lint([], repo_root=root, knob_rules=False,
                           thread_roots=roots or self.ROOTS)

    def test_seeded_race_flagged(self, tmp_path):
        # put() mutates _owner bare while drain() holds _lock: no
        # COMMON lock across the sites -> a real interleaving window
        root = str(tmp_path)
        _write(root, "race.py", """
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._owner = {}

                def put(self, uid):
                    self._owner[uid] = 1

                def drain(self, uid):
                    with self._lock:
                        self._owner.pop(uid, None)
        """)
        findings = self._lint(root)
        assert [f.rule for f in findings] == ["DSL007"]
        assert "_owner" in findings[0].message
        assert "no common self.* lock" in findings[0].message

    def test_properly_locked_clean(self, tmp_path):
        root = str(tmp_path)
        _write(root, "race.py", """
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._owner = {}

                def put(self, uid):
                    with self._lock:
                        self._owner[uid] = 1

                def drain(self, uid):
                    with self._lock:
                        self._owner.pop(uid, None)
        """)
        assert self._lint(root) == []

    def test_same_thread_group_never_races(self, tmp_path):
        # both roots registered in ONE group = sequential callers on a
        # single thread; bare mutation is fine
        root = str(tmp_path)
        _write(root, "race.py", """
            class Pool:
                def put(self, uid):
                    self._owner[uid] = 1

                def drain(self, uid):
                    self._owner.pop(uid, None)
        """)
        roots = {"race.py": {"Pool": {"put": "driver", "drain": "driver"}}}
        assert self._lint(root, roots) == []

    def test_non_self_lock_is_not_a_guard(self, tmp_path):
        # rep.lock serializes the REPLICA, not two pool methods: both
        # sites hold a lock, but not a common self.* one
        root = str(tmp_path)
        _write(root, "race.py", """
            import threading

            class Rep:
                def __init__(self):
                    self.lock = threading.Lock()

            class Pool:
                def put(self, rep):
                    with rep.lock:
                        self._owner[1] = 1

                def drain(self, rep):
                    with rep.lock:
                        self._owner[2] = 2
        """)
        findings = self._lint(root)
        assert [f.rule for f in findings] == ["DSL007"]
        assert "_owner" in findings[0].message

    def test_transitive_race_through_helper(self, tmp_path):
        # the bare mutation lives in a helper the root reaches through
        # the call graph — the race is still attributed to the roots
        root = str(tmp_path)
        _write(root, "race.py", """
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()

                def _mint(self):
                    self._n += 1

                def put(self):
                    self._mint()

                def drain(self):
                    with self._lock:
                        self._n = 0
        """)
        findings = self._lint(root)
        assert [f.rule for f in findings] == ["DSL007"]
        assert "'Pool._n'" in findings[0].message

    def test_lock_order_inversion(self, tmp_path):
        root = str(tmp_path)
        _write(root, "race.py", """
            import threading

            class Pool:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def put(self):
                    with self._a:
                        with self._b:
                            self.x = 1

                def drain(self):
                    with self._b:
                        with self._a:
                            self.x = 2
        """)
        findings = self._lint(root)
        inversions = [f for f in findings
                      if "lock-order inversion" in f.message]
        assert len(inversions) == 1
        assert "self._a" in inversions[0].message
        assert "self._b" in inversions[0].message
        # x is written under BOTH locks on both paths -> no (a) race
        assert not any("no common self.* lock" in f.message
                       for f in findings)

    def test_readback_under_lock(self, tmp_path):
        # DSL001 predicate under a held lock: one device readback
        # stalls every thread queued on the lock
        root = str(tmp_path)
        _write(root, "race.py", """
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()

                def put(self, res):
                    with self._lock:
                        self._n = int(res[0])
        """)
        findings = self._lint(root)
        assert [f.rule for f in findings] == ["DSL007"]
        assert "while holding" in findings[0].message
        assert "self._lock" in findings[0].message

    def test_justified_allow_suppresses(self, tmp_path):
        root = str(tmp_path)
        _write(root, "race.py", """
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()

                def put(self, res):
                    with self._lock:
                        # host int from the drain manifest, no device
                        # handle in reach  # dslint: allow(DSL007)
                        self._n = int(res[0])
        """)
        assert self._lint(root) == []


class TestCollectiveBudgetRule:
    """DSL008 golden fixtures — synthetic SITE_BUDGETS over tmp trees
    (the real registry in deepspeed_tpu/analysis/budgets.py is enforced
    by TestRepoClean)."""

    CODE = """
        from jax import lax

        def _inner(x):
            return lax.psum(x, "model")

        def builder(x):
            y = lax.ppermute(x, "seq", [(0, 1)])
            return _inner(y)

        def stray(x):
            return lax.all_gather(x, "model")
    """

    def _lint(self, root, budgets):
        return dslint.lint([], repo_root=root, knob_rules=False,
                           site_budgets=budgets)

    def test_registered_budgets_clean(self, tmp_path):
        # builder's psum is reached TRANSITIVELY through _inner — the
        # call-graph closure, not just direct sites
        root = str(tmp_path)
        _write(root, "b.py", self.CODE)
        budgets = {"b.py": {"builder": {"ppermute": 1, "psum": 1},
                            "stray": {"all_gather": 1}}}
        assert self._lint(root, budgets) == []

    def test_stray_collective_flagged(self, tmp_path):
        root = str(tmp_path)
        _write(root, "b.py", self.CODE)
        budgets = {"b.py": {"builder": {"ppermute": 1, "psum": 1}}}
        findings = self._lint(root, budgets)
        assert [f.rule for f in findings] == ["DSL008"]
        assert "unregistered collective: all_gather" in findings[0].message

    def test_budget_mismatch_flagged_at_builder(self, tmp_path):
        root = str(tmp_path)
        _write(root, "b.py", self.CODE)
        budgets = {"b.py": {"builder": {"ppermute": 2, "psum": 1},
                            "stray": {"all_gather": 1}}}
        findings = self._lint(root, budgets)
        assert [f.rule for f in findings] == ["DSL008"]
        assert "budget mismatch for 'builder'" in findings[0].message
        assert "'ppermute': 2" in findings[0].message   # registry side
        assert "'ppermute': 1" in findings[0].message   # call-graph side

    def test_missing_builder_flagged(self, tmp_path):
        root = str(tmp_path)
        _write(root, "b.py", """
            from jax import lax

            def builder(x):
                return lax.psum(x, "model")
        """)
        budgets = {"b.py": {"builder": {"psum": 1},
                            "gone": {"psum": 1}}}
        findings = self._lint(root, budgets)
        assert [f.rule for f in findings] == ["DSL008"]
        assert "registered builder 'gone' not found" in findings[0].message

    def test_justified_allow_suppresses_stray(self, tmp_path):
        root = str(tmp_path)
        _write(root, "b.py", """
            from jax import lax

            def builder(x):
                return lax.psum(x, "model")

            def bench_probe(x):
                # bench-only probe, never jitted into a serve program
                # dslint: allow(DSL008)
                return lax.all_gather(x, "model")
        """)
        budgets = {"b.py": {"builder": {"psum": 1}}}
        assert self._lint(root, budgets) == []

    def test_jax_lax_dotted_receiver_counts(self, tmp_path):
        # jax.lax.psum (no from-import) resolves to the same kind
        root = str(tmp_path)
        _write(root, "b.py", """
            import jax

            def builder(x):
                return jax.lax.psum(x, "model")
        """)
        assert self._lint(root, {"b.py": {"builder": {"psum": 1}}}) == []
        findings = self._lint(root, {"b.py": {}})
        assert any("unregistered collective: psum" in f.message
                   for f in findings)


class TestCLIJsonAndChangedOnly:
    ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "tools"))

    def _run(self, args, cwd=None):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "bin", "dstpu_lint")]
            + args, capture_output=True, text=True, env=self.ENV, cwd=cwd)

    def test_json_reports_findings(self, tmp_path):
        import json
        root = str(tmp_path)
        _write(root, "deepspeed_tpu/inference/v2/m.py", """
            import jax
            f = jax.jit(lambda x: x)
        """)
        r = self._run(["deepspeed_tpu", "--no-knob-rules",
                       "--root", root, "--json"])
        assert r.returncode == 1
        out = json.loads(r.stdout)
        assert out["count"] == 1 and out["clean"] is False
        (f,) = out["findings"]
        assert f["rule"] == "DSL002"
        assert f["path"] == "deepspeed_tpu/inference/v2/m.py"
        assert f["line"] == 3

    def test_changed_only_scopes_to_git_diff(self, tmp_path):
        import json
        root = str(tmp_path)
        git = ["git", "-C", root, "-c", "user.email=t@t",
               "-c", "user.name=t"]
        subprocess.run(git + ["init", "-q"], check=True)
        _write(root, "deepspeed_tpu/inference/v2/old.py", """
            import jax
            f = jax.jit(lambda x: x)
        """)
        subprocess.run(git + ["add", "-A"], check=True)
        subprocess.run(git + ["commit", "-qm", "seed"], check=True)
        # untracked NEW violation: --changed-only reports it and ONLY it
        _write(root, "deepspeed_tpu/inference/v2/new.py", """
            import jax
            g = jax.jit(lambda x: x)
        """)
        r = self._run(["deepspeed_tpu", "--no-knob-rules", "--root", root,
                       "--json", "--changed-only"])
        out = json.loads(r.stdout)
        assert out["changed_only"] is True
        assert [f["path"] for f in out["findings"]] == \
            ["deepspeed_tpu/inference/v2/new.py"]
        # committed -> nothing changed -> fast clean exit, zero findings
        subprocess.run(git + ["add", "-A"], check=True)
        subprocess.run(git + ["commit", "-qm", "add"], check=True)
        r = self._run(["deepspeed_tpu", "--no-knob-rules", "--root", root,
                       "--json", "--changed-only"])
        assert r.returncode == 0
        out = json.loads(r.stdout)
        assert out["clean"] is True and out["findings"] == []


class TestSinglePassIndex:
    """The single-AST-pass acceptance criterion is asserted on the real
    repo inside TestRepoClean::test_deepspeed_tpu_lints_clean (an
    ast.parse spy over the full lint); here the cache mechanism."""

    def test_repo_index_caches(self, tmp_path):
        path = _write(str(tmp_path), "m.py", "x = 1\n")
        index = dslint.RepoIndex(str(tmp_path))
        fi1 = index.get(path)
        fi2 = index.get(path)
        assert fi1 is fi2
        assert index.parse_count == 1
