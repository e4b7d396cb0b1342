"""Tests for the static analyzer (``h2o3_tpu/analysis/``).

Each pass gets positive fixtures that MUST be flagged and negatives
that must NOT, plus suppression-comment and baseline round-trips, the
``--json`` schema, and the tier-1 gate: ``scripts/analyze.py`` must run
clean on the repo itself (a new unbaselined finding fails this suite).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from h2o3_tpu.analysis import core
from h2o3_tpu.analysis.core import (analyze_source, load_baseline,
                                    save_baseline, split_baselined)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ANALYZE = os.path.join(ROOT, "scripts", "analyze.py")

AST_PASSES = ["lock-discipline", "tracer-purity", "seeded-determinism",
              "knob-registry", "rpc-payload"]


def rules(findings):
    return [f.rule for f in findings]


def src(text):
    return textwrap.dedent(text)


# ---------------------------------------------------------------------------
# lock-discipline


class TestLockDiscipline:
    def test_sleep_under_lock_flagged(self):
        fs = analyze_source(src("""
            import threading, time
            _lock = threading.Lock()
            def f():
                with _lock:
                    time.sleep(5)
        """), pass_names=["lock-discipline"])
        assert rules(fs) == ["LOCK001"]
        assert fs[0].line == 6
        assert "time.sleep" in fs[0].message

    def test_sleep_after_lock_not_flagged(self):
        fs = analyze_source(src("""
            import threading, time
            _lock = threading.Lock()
            def f():
                with _lock:
                    x = 1
                time.sleep(5)
        """), pass_names=["lock-discipline"])
        assert fs == []

    def test_rpc_call_under_self_lock_flagged(self):
        fs = analyze_source(src("""
            import threading
            class Store:
                def __init__(self):
                    self._lock = threading.RLock()
                def f(self, client, addr):
                    with self._lock:
                        return client.call(addr, "dkv_get", {})
        """), pass_names=["lock-discipline"])
        assert rules(fs) == ["LOCK001"]
        assert fs[0].symbol == "Store.f"

    def test_blocking_via_local_call_propagates(self):
        fs = analyze_source(src("""
            import threading, subprocess
            _lock = threading.Lock()
            def helper():
                subprocess.run(["make"])
            def f():
                with _lock:
                    helper()
        """), pass_names=["lock-discipline"])
        assert rules(fs) == ["LOCK001"]
        assert "helper" in fs[0].message

    def test_device_dispatch_under_lock_flagged(self):
        fs = analyze_source(src("""
            import threading
            import jax.numpy as jnp
            _table_lock = threading.Lock()
            def f(arrays):
                with _table_lock:
                    return jnp.stack(arrays, axis=1)
        """), pass_names=["lock-discipline"])
        assert rules(fs) == ["LOCK001"]
        assert "jnp.stack" in fs[0].message

    def test_nested_def_under_lock_not_flagged(self):
        # a closure defined (not called) under the lock runs later
        fs = analyze_source(src("""
            import threading, time
            _lock = threading.Lock()
            def f():
                with _lock:
                    def later():
                        time.sleep(5)
                    return later
        """), pass_names=["lock-discipline"])
        assert fs == []

    def test_condition_wait_in_own_with_not_flagged(self):
        fs = analyze_source(src("""
            import threading
            qlock = threading.Condition()
            def f():
                with qlock:
                    qlock.wait(timeout=1)
        """), pass_names=["lock-discipline"])
        assert fs == []

    def test_lock_order_inversion_flagged(self):
        fs = analyze_source(src("""
            import threading
            a_lock = threading.Lock()
            b_lock = threading.Lock()
            def f():
                with a_lock:
                    with b_lock:
                        pass
            def g():
                with b_lock:
                    with a_lock:
                        pass
        """), pass_names=["lock-discipline"])
        assert "LOCK002" in rules(fs)

    def test_consistent_lock_order_not_flagged(self):
        fs = analyze_source(src("""
            import threading
            a_lock = threading.Lock()
            b_lock = threading.Lock()
            def f():
                with a_lock:
                    with b_lock:
                        pass
            def g():
                with a_lock:
                    with b_lock:
                        pass
        """), pass_names=["lock-discipline"])
        assert [r for r in rules(fs) if r == "LOCK002"] == []


# ---------------------------------------------------------------------------
# tracer-purity


class TestTracerPurity:
    def test_time_in_jitted_fn_flagged(self):
        fs = analyze_source(src("""
            import jax, time
            @jax.jit
            def f(x):
                t = time.time()
                return x + t
        """), pass_names=["tracer-purity"])
        assert rules(fs) == ["TRACE001"]
        assert fs[0].symbol == "f"

    def test_partial_jit_decorator_flagged(self):
        fs = analyze_source(src("""
            import jax, random
            from functools import partial
            @partial(jax.jit, static_argnums=0)
            def f(n, x):
                return x * random.random()
        """), pass_names=["tracer-purity"])
        assert rules(fs) == ["TRACE001"]

    def test_fn_passed_to_map_reduce_flagged(self):
        fs = analyze_source(src("""
            def shard_fn(cols, mask):
                COUNTER.inc()
                return cols
            def run(table):
                return map_reduce(shard_fn, table)
        """), pass_names=["tracer-purity"])
        assert rules(fs) == ["TRACE001"]
        assert "telemetry" in fs[0].message

    def test_emit_lambda_flagged(self):
        fs = analyze_source(src("""
            import time
            SPEC = prim("badop", fusible=True,
                        emit=lambda jnp, a: a * time.time())
        """), pass_names=["tracer-purity"])
        assert rules(fs) == ["TRACE001"]
        assert "emit" in fs[0].message

    def test_functional_at_set_not_flagged(self):
        # arr.at[i].set(v) is functional jax, not telemetry
        fs = analyze_source(src("""
            import jax
            @jax.jit
            def f(x):
                return x.at[0].set(1.0)
        """), pass_names=["tracer-purity"])
        assert fs == []

    def test_untraced_fn_not_flagged(self):
        fs = analyze_source(src("""
            import time
            def plain():
                return time.time()
        """), pass_names=["tracer-purity"])
        assert fs == []


# ---------------------------------------------------------------------------
# seeded-determinism


class TestSeededDeterminism:
    FAULTS = "h2o3_tpu/cluster/faults.py"

    def test_bare_random_in_scope_flagged(self):
        fs = analyze_source(src("""
            import random
            def should_drop():
                return random.random() < 0.5
        """), rel=self.FAULTS, pass_names=["seeded-determinism"])
        assert rules(fs) == ["SEED001"]

    def test_unseeded_random_instance_flagged(self):
        fs = analyze_source(src("""
            import random
            RNG = random.Random()
        """), rel=self.FAULTS, pass_names=["seeded-determinism"])
        assert rules(fs) == ["SEED002"]

    def test_wallclock_in_chaos_file_flagged(self):
        fs = analyze_source(src("""
            import time
            def jitter():
                return time.time() % 1.0
        """), rel="scripts/chaos.py", pass_names=["seeded-determinism"])
        assert rules(fs) == ["SEED003"]

    def test_seeded_random_not_flagged(self):
        fs = analyze_source(src("""
            import random
            def rule_rng(seed, i):
                return random.Random((seed << 16) ^ i)
        """), rel=self.FAULTS, pass_names=["seeded-determinism"])
        assert fs == []

    def test_out_of_scope_file_not_flagged(self):
        fs = analyze_source(src("""
            import random
            def sample():
                return random.random()
        """), rel="h2o3_tpu/models/foo.py",
            pass_names=["seeded-determinism"])
        assert fs == []


# ---------------------------------------------------------------------------
# knob-registry


class TestKnobRegistry:
    def test_undocumented_read_flagged(self):
        fs = analyze_source(src("""
            import os
            V = os.environ.get("H2O3_TPU_FAKE_KNOB", "1")
        """), pass_names=["knob-registry"], readme_text="no knobs here")
        assert rules(fs) == ["KNOB001"]
        assert "H2O3_TPU_FAKE_KNOB" in fs[0].message

    def test_documented_read_not_flagged(self):
        fs = analyze_source(src("""
            import os
            V = os.environ.get("H2O3_TPU_FAKE_KNOB", "1")
        """), pass_names=["knob-registry"],
            readme_text="set `H2O3_TPU_FAKE_KNOB` to tune it")
        assert fs == []

    def test_config_table_constant_counts_as_read(self):
        fs = analyze_source(src("""
            KNOBS = {"workers": ("H2O3_TPU_FAKE_TABLE_KNOB", 16, int)}
        """), pass_names=["knob-registry"],
            readme_text="`H2O3_TPU_FAKE_TABLE_KNOB` documented")
        assert fs == []

    def test_documented_but_never_read_flagged(self):
        fs = analyze_source(src("""
            import os
        """), pass_names=["knob-registry"],
            readme_text="tune `H2O3_TPU_GHOST_KNOB` for speed")
        assert rules(fs) == ["KNOB002"]
        assert fs[0].file == "README.md"
        assert fs[0].symbol == "H2O3_TPU_GHOST_KNOB"

    def test_struck_knobs_stay_struck(self):
        """The two rules over the tree itself, for the three names that had
        one value everywhere and went: no file of the package names one,
        and README.md documents none. The count of names only falls
        (PERF.md section 3)."""
        import re

        names = set()
        for rel in core.iter_source_files(ROOT, roots=("h2o3_tpu",)):
            with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
                names.update(re.findall(r"H2O3_TPU_[A-Z0-9_]+", f.read()))
        with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as f:
            readme = f.read()
        for knob in ("H2O3_TPU_HIST_DTYPE", "H2O3_TPU_HIST_FACT_MAX_KC",
                     "H2O3_TPU_HIST_NODE_BUCKETS"):
            assert knob not in names, knob
            assert knob not in readme, knob
        assert len(names) <= 67, sorted(names)


# ---------------------------------------------------------------------------
# rpc-payload


class TestRpcPayload:
    def test_lambda_to_store_put_flagged(self):
        fs = analyze_source(src("""
            def f(store):
                store.put("k", lambda x: x + 1)
        """), pass_names=["rpc-payload"])
        assert rules(fs) == ["ROUTE001"]

    def test_local_function_to_remote_put_flagged(self):
        fs = analyze_source(src("""
            def reducer(a, b):
                return a + b
            def f(router):
                router.remote_put("k", reducer, 2)
        """), pass_names=["rpc-payload"])
        assert rules(fs) == ["ROUTE001"]
        assert "reducer" in fs[0].message

    def test_plain_data_put_not_flagged(self):
        fs = analyze_source(src("""
            def f(store):
                store.put("k", {"rows": [1, 2, 3]})
        """), pass_names=["rpc-payload"])
        assert fs == []

    def test_local_queue_put_not_flagged(self):
        # q.put(...) is a local queue, not a wire crossing
        fs = analyze_source(src("""
            def f(q):
                q.put("k", lambda x: x)
        """), pass_names=["rpc-payload"])
        assert fs == []

    def test_lambda_in_rpc_payload_flagged(self):
        fs = analyze_source(src("""
            def f(client, addr):
                client.call(addr, "run_task", {"fn": lambda p: p})
        """), pass_names=["rpc-payload"])
        assert rules(fs) == ["ROUTE002"]

    def test_plain_rpc_payload_not_flagged(self):
        fs = analyze_source(src("""
            def f(client, addr):
                client.call(addr, "run_task", {"n": 3})
        """), pass_names=["rpc-payload"])
        assert fs == []


# ---------------------------------------------------------------------------
# telemetry-drift (README-parsing side; the live-registry side is
# covered by the tier-1 gate below and scripts/check_telemetry.py)


class TestTelemetryDrift:
    def test_ghost_metric_detected(self, tmp_path):
        from h2o3_tpu.analysis.passes import telemetry_drift as td
        readme = tmp_path / "README.md"
        readme.write_text(
            "## Observability\n\nwe export `ghost_metric_total` here\n")
        documented = td.readme_documented_metrics(str(readme))
        assert "ghost_metric_total" in documented
        # against any registry lacking it, the drift is a failure
        assert documented - {"real_metric_total"} == {"ghost_metric_total"}

    def test_route_table_parsed(self, tmp_path):
        from h2o3_tpu.analysis.passes import telemetry_drift as td
        readme = tmp_path / "README.md"
        readme.write_text(
            "## Observability\n\n"
            "| Route | What |\n|---|---|\n"
            "| `GET /3/Ping` | liveness |\n")
        assert td.readme_documented_routes(str(readme)) == {
            ("GET", "/3/Ping")}

    @pytest.mark.slow
    def test_collect_flags_doctored_readme(self, tmp_path):
        from h2o3_tpu.analysis.passes import telemetry_drift as td
        with open(os.path.join(ROOT, "README.md")) as f:
            text = f.read()
        doctored = text.replace(
            "## Observability\n",
            "## Observability\n\nbogus `h2o3_ghost_metric_total` ref\n", 1)
        readme = tmp_path / "README.md"
        readme.write_text(doctored)
        failures, _ = td.collect(ROOT, str(readme))
        assert any(sym == "h2o3_ghost_metric_total"
                   for _r, _f, sym, _m in failures)


# ---------------------------------------------------------------------------
# suppressions + baseline


LOCK_FIXTURE = """
import threading, time
_lock = threading.Lock()
def f():
    with _lock:
        time.sleep(5)
"""


class TestSuppression:
    def test_noqa_on_line_suppresses(self):
        fs = analyze_source(src("""
            import threading, time
            _lock = threading.Lock()
            def f():
                with _lock:
                    time.sleep(5)  # h2o3: noqa[LOCK001]
        """), pass_names=["lock-discipline"])
        assert fs == []

    def test_noqa_on_preceding_line_suppresses(self):
        fs = analyze_source(src("""
            import threading, time
            _lock = threading.Lock()
            def f():
                with _lock:
                    # h2o3: noqa[LOCK001]
                    time.sleep(5)
        """), pass_names=["lock-discipline"])
        assert fs == []

    def test_noqa_for_other_rule_does_not_suppress(self):
        fs = analyze_source(src("""
            import threading, time
            _lock = threading.Lock()
            def f():
                with _lock:
                    time.sleep(5)  # h2o3: noqa[TRACE001]
        """), pass_names=["lock-discipline"])
        assert rules(fs) == ["LOCK001"]

    def test_bare_noqa_suppresses_everything(self):
        fs = analyze_source(src("""
            import threading, time
            _lock = threading.Lock()
            def f():
                with _lock:
                    time.sleep(5)  # h2o3: noqa
        """), pass_names=["lock-discipline"])
        assert fs == []


class TestBaseline:
    def test_round_trip(self, tmp_path):
        fs = analyze_source(src(LOCK_FIXTURE),
                            pass_names=["lock-discipline"])
        assert len(fs) == 1
        path = str(tmp_path / "baseline.json")
        save_baseline(path, fs, {fs[0].fingerprint: "known and accepted"})
        baseline = load_baseline(path)
        new, accepted = split_baselined(fs, baseline)
        assert new == [] and len(accepted) == 1
        assert baseline[fs[0].fingerprint]["justification"] == \
            "known and accepted"

    def test_fingerprint_survives_line_drift(self, tmp_path):
        fs1 = analyze_source(src(LOCK_FIXTURE),
                             pass_names=["lock-discipline"])
        # unrelated lines added above the finding must not invalidate it
        shifted = "# a new comment\nX = 1\n" + src(LOCK_FIXTURE)
        fs2 = analyze_source(shifted, pass_names=["lock-discipline"])
        assert fs1[0].line != fs2[0].line
        assert fs1[0].fingerprint == fs2[0].fingerprint

    def test_new_finding_not_matched(self, tmp_path):
        fs = analyze_source(src(LOCK_FIXTURE),
                            pass_names=["lock-discipline"])
        path = str(tmp_path / "baseline.json")
        save_baseline(path, [])
        new, accepted = split_baselined(fs, load_baseline(path))
        assert len(new) == 1 and accepted == []

    def test_version_check(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text('{"version": 99, "entries": []}')
        with pytest.raises(ValueError):
            load_baseline(str(path))


# ---------------------------------------------------------------------------
# driver / CI gate


class TestDriver:
    def run_analyze(self, *args, timeout=240):
        return subprocess.run(
            [sys.executable, ANALYZE, *args], cwd=ROOT,
            capture_output=True, text=True, timeout=timeout)

    @pytest.mark.slow
    def test_repo_runs_clean(self):
        """THE tier-1 gate: any new unbaselined finding fails the suite."""
        proc = self.run_analyze()
        assert proc.returncode == 0, \
            f"analyzer found new issues:\n{proc.stdout}\n{proc.stderr}"
        assert "analyze: OK" in proc.stdout

    def test_repo_runs_clean_ast_passes(self):
        """Fast gate over the pure-AST passes (no runtime imports)."""
        proc = self.run_analyze("--passes", ",".join(AST_PASSES))
        assert proc.returncode == 0, \
            f"analyzer found new issues:\n{proc.stdout}\n{proc.stderr}"

    def test_baseline_is_nonempty_and_justified(self):
        baseline = load_baseline(
            os.path.join(ROOT, "analysis_baseline.json"))
        assert baseline, "checked-in baseline must be non-empty"
        for entry in baseline.values():
            assert entry["justification"].strip(), \
                f"baseline entry {entry['fingerprint']} lacks justification"

    def test_json_schema(self, tmp_path):
        fixture = tmp_path / "fixture.py"
        fixture.write_text(src(LOCK_FIXTURE))
        empty = tmp_path / "baseline.json"
        proc = self.run_analyze(
            "--json", "--passes", "lock-discipline",
            "--baseline", str(empty), str(fixture))
        data = json.loads(proc.stdout)
        assert proc.returncode == 1
        assert data["version"] == 1
        assert data["baselined"] == 0
        assert data["passes"] == ["lock-discipline"]
        (finding,) = data["findings"]
        assert set(finding) == {"rule", "file", "line", "symbol",
                                "message", "snippet", "fingerprint"}
        assert finding["rule"] == "LOCK001"

    def test_changed_only_mode(self):
        proc = self.run_analyze("--changed-only", "--passes",
                                ",".join(AST_PASSES))
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_exit_nonzero_on_new_finding(self, tmp_path):
        fixture = tmp_path / "fixture.py"
        fixture.write_text(src(LOCK_FIXTURE))
        empty = tmp_path / "baseline.json"
        proc = self.run_analyze("--passes", "lock-discipline",
                                "--baseline", str(empty), str(fixture))
        assert proc.returncode == 1
        assert "LOCK001" in proc.stdout

    def test_update_baseline_then_clean(self, tmp_path):
        fixture = tmp_path / "fixture.py"
        fixture.write_text(src(LOCK_FIXTURE))
        bl = tmp_path / "baseline.json"
        proc = self.run_analyze("--passes", "lock-discipline",
                                "--baseline", str(bl),
                                "--update-baseline", str(fixture))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        proc = self.run_analyze("--passes", "lock-discipline",
                                "--baseline", str(bl), str(fixture))
        assert proc.returncode == 0, proc.stdout + proc.stderr


# in-repo regression: the shipped sources the analyzer protects must
# keep satisfying the specific invariants fixed in this change
class TestShippedInvariants:
    def test_keyed_store_analyzer_clean(self):
        with open(os.path.join(ROOT, "h2o3_tpu", "keyed.py")) as f:
            fs = analyze_source(f.read(), rel="h2o3_tpu/keyed.py",
                                pass_names=["lock-discipline"])
        assert fs == [], [f.render() for f in fs]

    def test_mapreduce_matrix_analyzer_clean(self):
        path = os.path.join(ROOT, "h2o3_tpu", "compute", "mapreduce.py")
        with open(path) as f:
            fs = analyze_source(
                f.read(), rel="h2o3_tpu/compute/mapreduce.py",
                pass_names=["lock-discipline"])
        assert fs == [], [f.render() for f in fs]
