"""paddle-analyze: the unified static-analysis framework (ISSUE 7).

The contracts under test:
  * FRAMEWORK — one walker (pycache/exempt handling), ONE AST parse per
    file shared by all rules, unified `# <layer>: ok (<why>)` markers
    (bare marker = finding M1), per-rule allowlists, SYNTAX findings,
    unknown-rule rejection.
  * RULES — every rule (R1-R3, O1-O5, A1-A8, M1) has a triggering fixture
    AND a near-miss that must stay clean. The ISSUE-15 passes: A6
    lock-order (cycle / self-reacquire vs consistent order), A7
    blocking-under-lock (sleep/urlopen/queue.get/one-hop socket send vs
    after-release), A8 wire-contract registry (undeclared route/status/
    branch/unnamed-by-test vs clean), each with the --changed
    cross-file-globality contract.
  * DRIVER — `python -m tools.analyze` exits 0 on the repo against the
    committed baseline; --rules/--json/--changed/--fix-markers/--env-table
    work; deleting the rank guard from an A1 fixture / registering a
    duplicate chaos site (A2) flips the exit code.
  * BASELINE — entries need written reasons (reasonless = config error),
    matched findings are suppressed, stale entries are listed by
    --fix-markers (the baseline only ever shrinks).
  * REGISTRIES — chaos.SITES runtime mirror (unregistered site warns and
    records a flight event, never raises); env_flags declared defaults;
    the README env table is generated and staleness-checked.
  * REGRESSIONS — the two real races the A5 pass surfaced (ISSUE 7:
    slo.RequestTracker.breached and fleet.TelemetryClient._cmd_off
    unlocked read-modify-writes) stay fixed: concurrency tests pin the
    exact counts, and fixtures replicating the old buggy shape still trip
    A5.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
import threading

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.analyze import run  # noqa: E402
from tools.analyze.__main__ import env_table, main as analyze_main  # noqa: E402
from tools.analyze.core import FileCtx, edit_distance_1, walk_repo  # noqa: E402
from tools.analyze.registry import get_rules  # noqa: E402


def write_tree(root, files: dict) -> str:
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(root)


def rule_ids(findings) -> list[str]:
    return sorted({f.rule for f in findings})


def analyze_run(*args, capsys=None):
    """(rc, stdout) from the driver in-process."""
    rc = analyze_main(list(args))
    out = capsys.readouterr().out if capsys is not None else ""
    return rc, out


def analyze_cli(*args, cwd=REPO):
    """The real CLI (fresh interpreter) — used where the subprocess
    contract itself is under test; fixture tests use analyze_main
    in-process to keep tier-1 wall time down."""
    return subprocess.run([sys.executable, "-m", "tools.analyze", *args],
                          capture_output=True, text=True, cwd=cwd,
                          timeout=180)


# ------------------------------------------------------------- framework

class TestFramework:
    def test_walker_scope(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/a.py": "x = 1\n",
            "paddle_tpu/sub/b.py": "y = 2\n",
            "paddle_tpu/__pycache__/c.py": "junk(\n",
            "bench.py": "z = 3\n",
            "benchmarks/d.py": "w = 4\n",
            "unrelated/e.py": "v = 5\n",
        })
        rels = walk_repo(str(tmp_path))
        assert rels == ["bench.py", "benchmarks/d.py", "paddle_tpu/a.py",
                        "paddle_tpu/sub/b.py"]

    def test_ast_parsed_once_per_file(self, tmp_path):
        write_tree(tmp_path, {"paddle_tpu/a.py": "x = 1\n"})
        ctx = FileCtx(str(tmp_path), "paddle_tpu/a.py")
        assert ctx.tree is ctx.tree  # cached object, not a re-parse

    def test_syntax_error_is_one_finding(self, tmp_path):
        write_tree(tmp_path, {"paddle_tpu/bad.py": "def f(:\n"})
        findings = run(str(tmp_path))
        assert [f.rule for f in findings] == ["SYNTAX"]
        assert findings[0].path == "paddle_tpu/bad.py"

    def test_unknown_rule_rejected(self):
        with pytest.raises(KeyError):
            get_rules(["NOPE"])
        assert analyze_main([str(REPO), "--rules", "NOPE"]) == 2

    def test_marker_with_reason_suppresses_each_layer(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/distributed/x.py":
                "import jax\n"
                "def f(t, rank):\n"
                "    jax.block_until_ready(t)  # resilience: ok (audited)\n"
                "    if rank == 0:\n"
                "        barrier()  # spmd: ok (sub-group of exactly rank 0's peers)\n",
        })
        assert run(str(tmp_path), rule_ids=["R3", "A1"]) == []

    def test_bare_marker_is_m1_finding(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/x.py":
                "a = 1  # resilience: ok\n"
                "b = 2  # locks: ok ()\n"
                "c = 3  # locks: ok (single-threaded by construction)\n"
                "d = 4  # not-a-layer: ok\n",
        })
        findings = run(str(tmp_path), rule_ids=["M1"])
        assert [f.line for f in findings] == [1, 2]


# ---------------------------------------------------- fixtures: R rules

class TestResilienceRuleFixtures:
    def test_r1_bad_and_near_miss(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/bad.py":
                "import time\n"
                "def f():\n"
                "    while True:\n"
                "        try:\n"
                "            return work()\n"
                "        except Exception:\n"
                "            time.sleep(1)\n",
            "paddle_tpu/near.py":  # sleep-only pacing loop, no try/except
                "import time\n"
                "def g():\n"
                "    for _ in range(3):\n"
                "        time.sleep(0.1)\n",
        })
        findings = run(str(tmp_path), rule_ids=["R1"])
        assert [(f.path, f.rule) for f in findings] == \
            [("paddle_tpu/bad.py", "R1")]

    def test_r2_bad_and_near_miss(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/bad.py":
                "import os, time\n"
                "def f(p):\n"
                "    while not os.path.exists(p):\n"
                "        time.sleep(0.1)\n",
            "paddle_tpu/near.py":  # exists check without the sleep
                "import os\n"
                "def g(p):\n"
                "    while not os.path.exists(p):\n"
                "        pass\n",
        })
        findings = run(str(tmp_path), rule_ids=["R2"])
        assert [(f.path, f.rule) for f in findings] == \
            [("paddle_tpu/bad.py", "R2")]

    def test_r3_bad_and_near_miss(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/distributed/bad.py":
                "import jax\n"
                "def f(t):\n"
                "    jax.block_until_ready(t)\n",
            "paddle_tpu/distributed/near.py":
                "import jax\n"
                "from w import watch\n"
                "def g(t):\n"
                "    with watch('barrier'):\n"
                "        jax.block_until_ready(t)\n",
            "paddle_tpu/models/outside_scope.py":
                "import jax\n"
                "def h(t):\n"
                "    jax.block_until_ready(t)\n",
        })
        findings = run(str(tmp_path), rule_ids=["R3"])
        assert [(f.path, f.rule) for f in findings] == \
            [("paddle_tpu/distributed/bad.py", "R3")]


# ---------------------------------------------------- fixtures: O rules

class TestObservabilityRuleFixtures:
    def test_o1_o2_bad_and_near_miss(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/bad.py":
                "import time\n"
                "def f():\n"
                "    t0 = time.time()\n"
                "    print('took', time.time() - t0)\n",
            "paddle_tpu/near.py":  # perf_counter math is legal outside O4
                "import time\n"
                "def g():\n"
                "    t0 = time.perf_counter()\n"
                "    return time.perf_counter() - t0\n",
            "paddle_tpu/observability/layer.py":  # the layer is exempt
                "print('echo path')\n",
        })
        findings = run(str(tmp_path), rule_ids=["O1", "O2"])
        assert rule_ids(findings) == ["O1", "O2"]
        assert {f.path for f in findings} == {"paddle_tpu/bad.py"}

    def test_o3_bad_and_near_miss(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/bad.py": "import urllib.request\n",
            "paddle_tpu/near.py": "import urllib.parse\n",  # string munging
        })
        findings = run(str(tmp_path), rule_ids=["O3"])
        assert [(f.path, f.rule) for f in findings] == \
            [("paddle_tpu/bad.py", "O3")]

    def test_o4_bad_and_near_miss(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/inference/bad.py":
                "import time\nt = time.perf_counter()\n",
            "paddle_tpu/models/near.py":  # same call outside O4's scope
                "import time\nt = time.perf_counter()\n",
        })
        findings = run(str(tmp_path), rule_ids=["O4"])
        assert [(f.path, f.rule) for f in findings] == \
            [("paddle_tpu/inference/bad.py", "O4")]

    def test_o5_req_span_namespace_bad_and_near_misses(self, tmp_path):
        """O5: a req.* add_span outside slo.py/reqtrace.py (literal OR
        module-constant name) is a finding — the taxonomy is
        single-sourced. Near misses stay clean: a non-req namespace, a
        dynamic name the resolver can't prove, a marked line, and the
        two sanctioned source files themselves."""
        write_tree(tmp_path, {
            "paddle_tpu/inference/bad.py":
                "from paddle_tpu.observability import spans\n"
                "spans.add_span('req.sideband', 'request', 0.0, 1.0)\n",
            "paddle_tpu/inference/bad_const.py":  # constant resolves too
                "from paddle_tpu.observability import spans\n"
                "NAME = 'req.detour'\n"
                "spans.add_span(NAME, 'request', 0.0, 1.0)\n",
            "paddle_tpu/inference/near_ns.py":  # not the req.* namespace
                "from paddle_tpu.observability import spans\n"
                "spans.add_span('request.foo', 'request', 0.0, 1.0)\n"
                "spans.add_span('reqx', 'request', 0.0, 1.0)\n",
            "paddle_tpu/inference/near_dyn.py":  # dynamic: unprovable
                "from paddle_tpu.observability import spans\n"
                "def f(name):\n"
                "    spans.add_span(name, 'request', 0.0, 1.0)\n",
            "paddle_tpu/inference/near_marked.py":
                "from paddle_tpu.observability import spans\n"
                "spans.add_span('req.audited', 'request', 0.0, 1.0)"
                "  # observability: ok (audited one-off)\n",
            "paddle_tpu/observability/slo.py":  # the sanctioned sources
                "import spans\n"
                "spans.add_span('req.queue', 'request', 0.0, 1.0)\n",
            "paddle_tpu/observability/reqtrace.py":
                "import spans\n"
                "spans.add_span('req', 'request', 0.0, 1.0)\n",
        })
        findings = run(str(tmp_path), rule_ids=["O5"])
        assert sorted((f.path, f.rule) for f in findings) == \
            [("paddle_tpu/inference/bad.py", "O5"),
             ("paddle_tpu/inference/bad_const.py", "O5")]
        assert all("single-sourced" in f.message for f in findings)


# ---------------------------------------------------- fixtures: A1 spmd

_A1_GUARDED = """\
    from .env import get_rank
    def sync(t):
        if get_rank() == 0:
            barrier()
"""
_A1_CLEAN = """\
    from .env import get_rank
    def sync(t):
        barrier()
        if get_rank() == 0:
            log_something()
"""


class TestSpmdDivergentCollective:
    def test_rank_guarded_collective_flagged(self, tmp_path):
        write_tree(tmp_path,
                   {"paddle_tpu/distributed/comms.py": _A1_GUARDED})
        findings = run(str(tmp_path), rule_ids=["A1"])
        assert rule_ids(findings) == ["A1"]
        assert "barrier" in findings[0].message

    def test_near_misses_stay_clean(self, tmp_path):
        write_tree(tmp_path, {
            # unguarded collective + guarded non-collective
            "paddle_tpu/distributed/comms.py": _A1_CLEAN,
            # rank-guarded point-to-point is how pipelines work
            "paddle_tpu/distributed/p2p.py":
                "def exchange(t, rank):\n"
                "    if rank == 0:\n"
                "        send(t, dst=1)\n"
                "    else:\n"
                "        recv(t, src=0)\n",
            # non-rank guard around a collective
            "paddle_tpu/distributed/flagged.py":
                "def maybe(t, enabled):\n"
                "    if enabled:\n"
                "        all_reduce(t)\n",
            # outside distributed/**: out of scope
            "paddle_tpu/models/outside.py":
                "def f(t, rank):\n"
                "    if rank == 0:\n"
                "        all_reduce(t)\n",
        })
        assert run(str(tmp_path), rule_ids=["A1"]) == []

    def test_else_branch_and_self_rank_also_flagged(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/distributed/x.py":
                "def f(self, t):\n"
                "    if self.global_rank != 0:\n"
                "        pass\n"
                "    else:\n"
                "        all_gather(t)\n",
        })
        assert rule_ids(run(str(tmp_path), rule_ids=["A1"])) == ["A1"]

    def test_driver_flips_when_guard_added(self, tmp_path, capsys):
        # the acceptance drill: same tree, guard deleted <-> added
        root = write_tree(tmp_path,
                          {"paddle_tpu/distributed/comms.py": _A1_CLEAN})
        assert analyze_run(root, capsys=capsys)[0] == 0
        (tmp_path / "paddle_tpu/distributed/comms.py").write_text(
            textwrap.dedent(_A1_GUARDED))
        rc, out = analyze_run(root, capsys=capsys)
        assert rc == 1 and "[A1]" in out


# --------------------------------------------------- fixtures: A2 chaos

_CHAOS_REG = """\
    SITES = {
        "good.site": "a registered fault site",
    }
"""


class TestChaosSiteRegistry:
    def test_registered_literal_site_is_clean(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/distributed/resilience/chaos.py": _CHAOS_REG,
            "paddle_tpu/worker.py":
                "from .distributed.resilience import chaos\n"
                "def f():\n"
                "    chaos.hit(\"good.site\")\n",
            "tests/test_x.py": "SPEC = 'good.site:1'\n",
        })
        assert run(str(tmp_path), rule_ids=["A2"]) == []

    def test_unregistered_site_flagged(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/distributed/resilience/chaos.py": _CHAOS_REG,
            "paddle_tpu/worker.py":
                "from .distributed.resilience import chaos\n"
                "def f():\n"
                "    chaos.hit(\"rogue.site\")\n",
            "tests/test_x.py": "SPEC = 'good.site:1'\n",
        })
        findings = run(str(tmp_path), rule_ids=["A2"])
        assert any("rogue.site" in f.message for f in findings)

    def test_dynamic_site_flagged_near_miss_kwarg_ok(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/distributed/resilience/chaos.py": _CHAOS_REG,
            "paddle_tpu/worker.py":
                "from .distributed.resilience import chaos\n"
                "SITE = 'good.site'\n"
                "def f(registry):\n"
                "    chaos.hit(SITE)\n"          # name indirection: finding
                "    registry.hit(\"good.site\")\n",  # not the chaos module
            "tests/test_x.py": "SPEC = 'good.site:1'\n",
        })
        findings = run(str(tmp_path), rule_ids=["A2"])
        assert len(findings) == 1 and "non-literal" in findings[0].message
        assert findings[0].line == 4

    def test_duplicate_site_flips_driver(self, tmp_path, capsys):
        root = write_tree(tmp_path, {
            "paddle_tpu/distributed/resilience/chaos.py":
                "SITES = {\n"
                "    'dup.site': 'first',\n"
                "    'dup.site': 'second',\n"
                "}\n",
        })
        rc, out = analyze_run(root, capsys=capsys)
        assert rc == 1
        assert "[A2]" in out and "duplicate" in out

    def test_untested_site_flagged_only_with_tests_dir(self, tmp_path):
        files = {
            "paddle_tpu/distributed/resilience/chaos.py": _CHAOS_REG,
            "paddle_tpu/worker.py":
                "from .distributed.resilience import chaos\n"
                "def f():\n"
                "    chaos.hit(\"good.site\")\n",
        }
        write_tree(tmp_path / "no_tests", files)
        assert run(str(tmp_path / "no_tests"), rule_ids=["A2"]) == []
        files["tests/test_other.py"] = "x = 1\n"
        write_tree(tmp_path / "with_tests", files)
        findings = run(str(tmp_path / "with_tests"), rule_ids=["A2"])
        assert len(findings) == 1 and "named by no test" in findings[0].message

    def test_description_required(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/distributed/resilience/chaos.py":
                "SITES = {'bare.site': ''}\n",
            "tests/test_x.py": "SPEC = 'bare.site:1'\n",
        })
        findings = run(str(tmp_path), rule_ids=["A2"])
        assert len(findings) == 1 and "description" in findings[0].message


# ----------------------------------------------- fixtures: A3 telemetry

class TestTelemetryNameRegistry:
    def test_conflicting_instrument_types(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/a.py":
                "from .observability import metrics\n"
                "metrics.counter('x.total').inc()\n",
            "paddle_tpu/b.py":
                "from .observability import metrics\n"
                "metrics.gauge('x.total').set(1)\n",
        })
        findings = run(str(tmp_path), rule_ids=["A3"])
        assert len(findings) == 1
        assert "conflicting instrument types" in findings[0].message

    def test_timer_is_a_histogram(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/a.py":
                "from .observability import metrics\n"
                "with metrics.timer('step.time_s'):\n"
                "    pass\n"
                "metrics.counter('step.time_s').inc()\n",
        })
        findings = run(str(tmp_path), rule_ids=["A3"])
        assert len(findings) == 1
        assert "conflicting instrument types" in findings[0].message

    def test_case_insensitive_collision(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/a.py":
                "from .observability import metrics\n"
                "metrics.counter('serve.Tokens').inc()\n"
                "metrics.counter('serve.tokens').inc()\n",
        })
        findings = run(str(tmp_path), rule_ids=["A3"])
        assert len(findings) == 1
        assert "case-insensitively" in findings[0].message

    def test_bucket_shadow_and_sanitize_collision(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/a.py":
                "from .observability import metrics\n"
                "metrics.histogram('lat_s').observe(1)\n"
                "metrics.counter('lat_s_bucket').inc()\n"
                "metrics.gauge('serve.depth').set(1)\n"
                "metrics.gauge('serve_depth').set(1)\n",
        })
        findings = run(str(tmp_path), rule_ids=["A3"])
        msgs = " | ".join(f.message for f in findings)
        assert "shadows histogram" in msgs
        assert "same Prometheus exposition name" in msgs

    def test_near_miss_distinct_names_clean(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/a.py":
                "from .observability import metrics, spans\n"
                "metrics.counter('serve.tokens').inc()\n"
                "metrics.gauge('serve.tokens_per_s').set(0)\n"
                "metrics.histogram('serve.burst_time_s').observe(1)\n"
                "with spans.span('serve.burst'):\n"  # spans: own namespace
                "    pass\n",
        })
        assert run(str(tmp_path), rule_ids=["A3"]) == []

    def test_standard_declarations_feed_the_name_table(self):
        # the real metrics.py _STANDARD_* tuples are parsed as typed
        # declarations (repo-wide cleanliness itself is covered by the
        # whole-repo driver run in TestDriver)
        rule = get_rules(["A3"])[0]
        ctx = FileCtx(REPO, "paddle_tpu/observability/metrics.py")
        list(rule.check_file(ctx))
        assert "slo.ttft_s" in rule._metrics["histogram"]
        assert "serve.pages_in_use" in rule._metrics["gauge"]
        assert "slo.breach" in rule._metrics["counter"]


# ------------------------------------------------ fixtures: A4 envflags

_ENV_REG = """\
    def declare(name, default, doc):
        return name
    declare("PADDLE_GOOD_FLAG", "1", "a documented knob")
"""


class TestEnvFlagRegistry:
    def test_declared_and_used_is_clean(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/utils/env_flags.py": _ENV_REG,
            "paddle_tpu/a.py":
                "import os\n"
                "v = os.environ.get('PADDLE_GOOD_FLAG', '1')\n",
        })
        assert run(str(tmp_path), rule_ids=["A4"]) == []

    def test_undeclared_flag_flagged(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/utils/env_flags.py": _ENV_REG,
            "paddle_tpu/a.py":
                "import os\n"
                "v = os.environ.get('PADDLE_MYSTERY_KNOB')\n"
                "u = os.environ.get('PADDLE_GOOD_FLAG')\n",
        })
        findings = run(str(tmp_path), rule_ids=["A4"])
        assert len(findings) == 1
        assert "PADDLE_MYSTERY_KNOB" in findings[0].message

    def test_typo_detector_names_the_intended_flag(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/utils/env_flags.py": _ENV_REG,
            "paddle_tpu/a.py":
                "import os\n"
                "u = os.environ.get('PADDLE_GOOD_FLAG')\n"
                "v = os.environ.get('PADDLE_GOOD_FLAK')\n",
        })
        findings = run(str(tmp_path), rule_ids=["A4"])
        assert len(findings) == 1
        assert "typo" in findings[0].message
        assert "PADDLE_GOOD_FLAG" in findings[0].message

    def test_helper_wrapped_read_and_constant_count_as_use(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/utils/env_flags.py": _ENV_REG,
            "paddle_tpu/a.py":
                "ENV_X = 'PADDLE_GOOD_FLAG'\n"
                "def _env_float(name, default):\n"
                "    import os\n"
                "    return float(os.environ.get(name, '') or default)\n"
                "v = _env_float(ENV_X, 1.0)\n",
        })
        assert run(str(tmp_path), rule_ids=["A4"]) == []

    def test_dead_declaration_flagged(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/utils/env_flags.py":
                _ENV_REG + "    declare(\"PADDLE_DEAD_KNOB\", \"\", \"unused\")\n",
            "paddle_tpu/a.py":
                "import os\nv = os.environ.get('PADDLE_GOOD_FLAG')\n",
        })
        findings = run(str(tmp_path), rule_ids=["A4"])
        assert len(findings) == 1
        assert "PADDLE_DEAD_KNOB" in findings[0].message

    def test_edit_distance_helper(self):
        assert edit_distance_1("PADDLE_X", "PADDLE_Y")
        assert edit_distance_1("PADDLE_X", "PADDLE_XY")
        assert not edit_distance_1("PADDLE_X", "PADDLE_X")
        assert not edit_distance_1("PADDLE_X", "PADDLE_XYZ")

    def test_runtime_registry_defaults(self, monkeypatch):
        from paddle_tpu.utils import env_flags
        monkeypatch.delenv("PADDLE_RPC_TIMEOUT", raising=False)
        assert env_flags.get("PADDLE_RPC_TIMEOUT") == "300"
        assert env_flags.get_float("PADDLE_TELEMETRY_INTERVAL") == 0.5
        monkeypatch.setenv("PADDLE_TRIGGERS", "0")
        assert env_flags.get_bool("PADDLE_TRIGGERS") is False
        with pytest.raises(KeyError):
            env_flags.get("PADDLE_NOT_A_FLAG")
        with pytest.raises(ValueError):
            env_flags.declare("PADDLE_CHAOS", "", "duplicate declaration")
        assert all(f.doc for f in env_flags.FLAGS.values())
        assert len(env_flags.FLAGS) >= 55

    def test_readme_env_table_not_stale(self):
        table = env_table(REPO).strip()
        with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
            readme = f.read()
        assert "<!-- env-flags:begin -->" in readme, \
            "README lost its generated env-flags block"
        block = readme.split("<!-- env-flags:begin -->")[1] \
                      .split("<!-- env-flags:end -->")[0].strip()
        assert block == table, \
            "README env-flags table is stale: regenerate with " \
            "`python -m tools.analyze --env-table`"

    def test_readme_routes_table_not_stale(self):
        # the A8 twin of the env table: the README HTTP-route reference
        # is generated from inference/routes.py and must not drift
        from tools.analyze.__main__ import routes_table
        table = routes_table(REPO).strip()
        with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
            readme = f.read()
        assert "<!-- routes:begin -->" in readme, \
            "README lost its generated routes block"
        block = readme.split("<!-- routes:begin -->")[1] \
                      .split("<!-- routes:end -->")[0].strip()
        assert block == table, \
            "README routes table is stale: regenerate with " \
            "`python -m tools.analyze --routes-table`"


# --------------------------------------------------- fixtures: A5 locks

class TestLockDiscipline:
    def test_unlocked_rmw_in_lock_using_class(self, tmp_path):
        write_tree(tmp_path, {
            # the exact shape of the two real races this pass surfaced
            # (slo.RequestTracker.breached / fleet.TelemetryClient._cmd_off)
            "paddle_tpu/observability/bad.py":
                "import threading\n"
                "class Tracker:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "        self.breached = 0\n"
                "        self._off = 0\n"
                "    def retire(self, breach):\n"
                "        with self._lk:\n"
                "            pass\n"
                "        if breach:\n"
                "            self.breached += 1\n"
                "    def read(self, n):\n"
                "        self._off += n\n",
        })
        findings = run(str(tmp_path), rule_ids=["A5"])
        assert [f.line for f in findings] == [11, 13]
        assert all("read-modify-write" in f.message for f in findings)

    def test_split_locked_unlocked_mutation(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/observability/split.py":
                "import threading\n"
                "class Buf:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "        self._items = []\n"
                "    def add(self, x):\n"
                "        with self._lock:\n"
                "            self._items.append(x)\n"
                "    def drain(self):\n"
                "        out = self._items\n"
                "        self._items = []\n"
                "        return out\n",
        })
        findings = run(str(tmp_path), rule_ids=["A5"])
        assert len(findings) == 1 and findings[0].line == 11
        assert "WITHOUT" in findings[0].message

    def test_near_misses_stay_clean(self, tmp_path):
        write_tree(tmp_path, {
            # everything under the lock: clean
            "paddle_tpu/observability/good.py":
                "import threading\n"
                "class Good:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "        self._n = 0\n"
                "    def inc(self):\n"
                "        with self._lk:\n"
                "            self._n += 1\n",
            # no lock in the class: += is not a finding (single-threaded)
            "paddle_tpu/observability/nolock.py":
                "class Plain:\n"
                "    def __init__(self):\n"
                "        self.n = 0\n"
                "    def inc(self):\n"
                "        self.n += 1\n",
            # marked with a reason: audited
            "paddle_tpu/observability/marked.py":
                "import threading\n"
                "class Audited:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "        self.n = 0\n"
                "    def tick(self):\n"
                "        with self._lk:\n"
                "            pass\n"
                "        self.n += 1  # locks: ok (only the poll thread touches n)\n",
            # out of scope: models/ is not the concurrent surface (the
            # ISSUE-15 scope extension covers ALL of inference/**, so the
            # old paging-adjacent near-miss now correctly trips)
            "paddle_tpu/models/paging_x.py":
                "import threading\n"
                "class P:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "        self.n = 0\n"
                "    def f(self):\n"
                "        with self._lk:\n"
                "            pass\n"
                "        self.n += 1\n",
        })
        assert run(str(tmp_path), rule_ids=["A5"]) == []

    def test_extended_scope_covers_disagg_and_elastic(self, tmp_path):
        # ISSUE 15 satellite: the PR-7 file list grew to the whole
        # concurrent surface — a race in inference/disagg/** or
        # fleet/elastic.py is now in scope
        race = ("import threading\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "        self.n = 0\n"
                "    def f(self):\n"
                "        with self._lk:\n"
                "            pass\n"
                "        self.n += 1\n")
        write_tree(tmp_path, {
            "paddle_tpu/inference/disagg/coord_x.py": race,
            "paddle_tpu/distributed/fleet/elastic.py": race,
            "paddle_tpu/distributed/fleet/topology.py": race,  # not listed
        })
        findings = run(str(tmp_path), rule_ids=["A5"])
        assert sorted(f.path for f in findings) == [
            "paddle_tpu/distributed/fleet/elastic.py",
            "paddle_tpu/inference/disagg/coord_x.py"]


# ------------------------------------------------ fixtures: A6 lock-order

_A6_CYCLE = {
    # Cache takes its own lock then calls into Alloc (which locks);
    # Alloc's pressure path locks itself then reaches back into a Cache
    # lock — opposite orders, a deadlock one interleaving away
    "paddle_tpu/inference/cache_x.py": """\
        import threading
        class Cache:
            def __init__(self, alloc):
                self._lk = threading.Lock()
                self._alloc = alloc
            def match(self):
                with self._lk:
                    self._alloc.share()
        """,
    "paddle_tpu/inference/alloc_x.py": """\
        import threading
        class Alloc:
            def __init__(self):
                self._lk = threading.Lock()
            def share(self):
                with self._lk:
                    pass
            def pressure(self, cache):
                with self._lk:
                    with cache._lk:
                        pass
        """,
}


class TestLockOrder:
    def test_cross_file_cycle_flagged_with_both_sites(self, tmp_path):
        write_tree(tmp_path, _A6_CYCLE)
        findings = run(str(tmp_path), rule_ids=["A6"])
        assert len(findings) == 1
        msg = findings[0].message
        assert "cycle" in msg
        assert "Cache._lk -> Alloc._lk" in msg \
            and "Alloc._lk -> Cache._lk" in msg
        # both acquisition sites named (file:line each direction)
        assert "cache_x.py:" in msg and "alloc_x.py:" in msg

    def test_self_reacquire_is_its_own_finding(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/observability/t_x.py":
                "import threading\n"
                "class T:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "    def summary(self):\n"
                "        with self._lk:\n"
                "            return 1\n"
                "    def snapshot(self):\n"
                "        with self._lk:\n"
                "            return self.summary()\n",
        })
        findings = run(str(tmp_path), rule_ids=["A6"])
        assert len(findings) == 1
        assert "not reentrant" in findings[0].message
        assert "T.summary()" in findings[0].message

    def test_self_attr_chain_resolves_through_constructor_type(
            self, tmp_path):
        # the ISSUE-15 canonical shape: `self._cache._lk` acquired under
        # `self._lk`, the attribute's class pinned by its constructor
        # assignment — colliding with the cache's own call-edge back
        write_tree(tmp_path, {
            "paddle_tpu/inference/engine_x.py":
                "import threading\n"
                "from .cache_x import Cache\n"
                "class Engine:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "        self._cache = Cache(self)\n"
                "    def step(self):\n"
                "        with self._lk:\n"
                "            with self._cache._lk:\n"
                "                pass\n",
            "paddle_tpu/inference/cache_x.py":
                "import threading\n"
                "class Cache:\n"
                "    def __init__(self, eng):\n"
                "        self._lk = threading.Lock()\n"
                "        self._eng = eng\n"
                "    def evict(self):\n"
                "        with self._lk:\n"
                "            self._eng.on_evict()\n",
            "paddle_tpu/inference/engine_hooks_x.py":
                "import threading\n"
                "class EngineHooks:\n"
                "    pass\n",
        })
        # Engine.on_evict doesn't exist, so no reverse edge yet: clean
        assert run(str(tmp_path), rule_ids=["A6"]) == []
        # give Engine an on_evict that locks -> the cycle closes
        p = tmp_path / "paddle_tpu/inference/engine_x.py"
        p.write_text(p.read_text() +
                     "    def on_evict(self):\n"
                     "        with self._lk:\n"
                     "            pass\n")
        findings = run(str(tmp_path), rule_ids=["A6"])
        assert len(findings) == 1 and "cycle" in findings[0].message
        assert "Engine._lk -> Cache._lk" in findings[0].message

    def test_consistent_order_stays_clean(self, tmp_path):
        # same two locks, always Cache -> Alloc: an edge, not a cycle
        write_tree(tmp_path, {
            "paddle_tpu/inference/cache_x.py":
                _A6_CYCLE["paddle_tpu/inference/cache_x.py"],
            "paddle_tpu/inference/alloc_x.py": """\
                import threading
                class Alloc:
                    def __init__(self):
                        self._lk = threading.Lock()
                    def share(self):
                        with self._lk:
                            pass
                """,
        })
        assert run(str(tmp_path), rule_ids=["A6"]) == []

    def test_multi_item_with_opposite_orders(self, tmp_path):
        # `with a, b:` acquires left to right — two methods doing it in
        # opposite orders is the classic deadlock and must edge per ITEM
        write_tree(tmp_path, {
            "paddle_tpu/inference/multi_x.py":
                "import threading\n"
                "class M:\n"
                "    def __init__(self):\n"
                "        self._a_lk = threading.Lock()\n"
                "        self._b_lk = threading.Lock()\n"
                "    def one(self):\n"
                "        with self._a_lk, self._b_lk:\n"
                "            pass\n"
                "    def two(self):\n"
                "        with self._b_lk, self._a_lk:\n"
                "            pass\n",
        })
        findings = run(str(tmp_path), rule_ids=["A6"])
        assert len(findings) == 1 and "cycle" in findings[0].message
        assert "M._a_lk" in findings[0].message \
            and "M._b_lk" in findings[0].message

    def test_marker_on_inner_site_suppresses(self, tmp_path):
        files = dict(_A6_CYCLE)
        files["paddle_tpu/inference/alloc_x.py"] = \
            files["paddle_tpu/inference/alloc_x.py"].replace(
                "with cache._lk:",
                "with cache._lk:  # locks: ok (pressure path only runs "
                "single-threaded in the drain drill)")
        write_tree(tmp_path, files)
        assert run(str(tmp_path), rule_ids=["A6"]) == []

    def test_marker_on_callee_acquisition_suppresses_call_edge(
            self, tmp_path):
        # the finding's advice is "mark the audited inner site" — that
        # must also clear an edge built through a CALL into that site
        # (Alloc.share's own `with self._lk:` is the inner site here)
        files = dict(_A6_CYCLE)
        src = files["paddle_tpu/inference/alloc_x.py"]
        # share's own `with self._lk:` (the only one followed by `pass`
        # directly) is the inner site the cycle finding names
        needle = "with self._lk:\n                    pass"
        assert needle in src
        files["paddle_tpu/inference/alloc_x.py"] = src.replace(
            needle,
            "with self._lk:  # locks: ok (share never calls back into "
            "any holder)\n                    pass")
        write_tree(tmp_path, files)
        assert run(str(tmp_path), rule_ids=["A6"]) == []

    def test_changed_scope_cannot_miss_cross_file_edges(self, tmp_path):
        # the acquisition graph is global: a --changed walk restricted to
        # ONE file must still see the edge living in the other
        write_tree(tmp_path, _A6_CYCLE)
        full = run(str(tmp_path), rule_ids=["A6"])
        partial = run(str(tmp_path), rule_ids=["A6"],
                      files=["paddle_tpu/inference/cache_x.py"])
        assert [f.message for f in partial] == [f.message for f in full]


# ------------------------------------------- fixtures: A7 blocking-under-lock

class TestBlockingUnderLock:
    def test_sleep_under_lock_vs_after_release(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/inference/bad.py":
                "import threading, time\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "    def f(self):\n"
                "        with self._lk:\n"
                "            time.sleep(0.1)\n",
            "paddle_tpu/inference/near.py":  # sleep AFTER the release
                "import threading, time\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "    def f(self):\n"
                "        with self._lk:\n"
                "            pass\n"
                "        time.sleep(0.1)\n",
        })
        findings = run(str(tmp_path), rule_ids=["A7"])
        assert [(f.path, f.line) for f in findings] == \
            [("paddle_tpu/inference/bad.py", 7)]
        assert "time.sleep" in findings[0].message

    def test_one_hop_socket_send_the_elastic_regression_shape(self, tmp_path):
        # the REAL finding this pass surfaced (ISSUE 15): the KV server
        # answered a 400 while holding the store lock — wfile.write is a
        # socket send, so one slow reader stalls every KV op. The exact
        # pre-fix shape must keep tripping.
        write_tree(tmp_path, {
            "paddle_tpu/distributed/fleet/kv_x.py":
                "import threading\n"
                "class KVServer:\n"
                "    def __init__(self):\n"
                "        lock = threading.Lock()\n"
                "        class H:\n"
                "            def _send(self, code, body=b''):\n"
                "                self.wfile.write(body)\n"
                "            def do_PUT(self):\n"
                "                with lock:\n"
                "                    try:\n"
                "                        vn = int(self.headers.get('X'))\n"
                "                    except ValueError:\n"
                "                        return self._send(400)\n"
                "                return self._send(200)\n",
        })
        findings = run(str(tmp_path), rule_ids=["A7"])
        assert len(findings) == 1 and findings[0].line == 13
        assert "socket send" in findings[0].message

    def test_urlopen_and_unbounded_queue_get(self, tmp_path):
        write_tree(tmp_path, {
            "paddle_tpu/distributed/fleet/bad.py":
                "import threading, urllib.request\n"
                "class C:\n"
                "    def __init__(self, q):\n"
                "        self._lk = threading.Lock()\n"
                "        self._queue = q\n"
                "    def f(self):\n"
                "        with self._lk:\n"
                "            urllib.request.urlopen('http://x')\n"
                "    def g(self):\n"
                "        with self._lk:\n"
                "            return self._queue.get()\n",
            "paddle_tpu/distributed/fleet/near.py":
                "import threading\n"
                "class C:\n"
                "    def __init__(self, q, d):\n"
                "        self._lk = threading.Lock()\n"
                "        self._queue, self._d = q, d\n"
                "    def g(self):\n"
                "        with self._lk:\n"
                "            # bounded get + a dict .get are both fine\n"
                "            return self._queue.get(timeout=1), \\\n"
                "                self._d.get('k')\n",
        })
        findings = run(str(tmp_path), rule_ids=["A7"])
        assert [f.line for f in findings] == [8, 11]
        msgs = " | ".join(f.message for f in findings)
        assert "urlopen" in msgs and "unbounded" in msgs

    def test_marker_and_scope_near_misses(self, tmp_path):
        write_tree(tmp_path, {
            # audited: the lock is private to one thread by construction
            "paddle_tpu/observability/marked.py":
                "import threading, time\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "    def f(self):\n"
                "        with self._lk:\n"
                "            time.sleep(0.1)  # locks: ok (test-only pacing; no second thread exists)\n",
            # out of scope: models/ is not the concurrent surface
            "paddle_tpu/models/outside.py":
                "import threading, time\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "    def f(self):\n"
                "        with self._lk:\n"
                "            time.sleep(0.1)\n",
            # a callback DEFINED under a lock runs later, not under it
            "paddle_tpu/inference/deferred.py":
                "import threading, time\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "    def f(self):\n"
                "        with self._lk:\n"
                "            def cb():\n"
                "                time.sleep(0.1)\n"
                "            return cb\n",
            # ...and the same exemption one hop out: a method that only
            # DEFINES a blocking callback is not itself blocking, so
            # calling the factory under a lock is clean
            "paddle_tpu/inference/factory.py":
                "import threading, time\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "    def make_cb(self):\n"
                "        def cb():\n"
                "            time.sleep(0.1)\n"
                "        return cb\n"
                "    def f(self):\n"
                "        with self._lk:\n"
                "            return self.make_cb()\n",
        })
        assert run(str(tmp_path), rule_ids=["A7"]) == []


# ---------------------------- fixtures: A5/A6/A7 on the autoscale surface

class TestAutoscaleSurfaceInScope:
    """ISSUE 16: the autoscaler is a lock-using, HTTP-touching concurrent
    class living at ``paddle_tpu/inference/autoscale.py`` — exactly the
    surface A5/A6/A7 police. These fixtures pin that the scope covers it
    (and the warm-start module) by planting each defect class at those
    literal paths, plus the shipped files staying clean."""

    def test_a5_unlocked_hysteresis_counter_trips(self, tmp_path):
        # the one race an autoscaler must not have: hysteresis counters
        # bumped outside the decision lock double-count under a
        # concurrent status read
        write_tree(tmp_path, {
            "paddle_tpu/inference/autoscale.py":
                "import threading\n"
                "class Controller:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "        self._breach = 0\n"
                "    def tick(self, pressure):\n"
                "        with self._lk:\n"
                "            pass\n"
                "        if pressure > 1.0:\n"
                "            self._breach += 1\n",
        })
        findings = run(str(tmp_path), rule_ids=["A5"])
        assert len(findings) == 1 and findings[0].line == 10
        assert "read-modify-write" in findings[0].message

    def test_a6_controller_cache_inversion_trips(self, tmp_path):
        # controller holds its decision lock while asking the warm cache
        # to pack; the cache's eviction path locks itself then reads the
        # controller's ledger — opposite orders across the two modules
        write_tree(tmp_path, {
            "paddle_tpu/inference/autoscale.py": """\
                import threading
                class Controller:
                    def __init__(self, cache):
                        self._lk = threading.Lock()
                        self._cache = cache
                    def decide(self):
                        with self._lk:
                            self._cache.export()
                """,
            "paddle_tpu/inference/warmstart.py": """\
                import threading
                class WarmCache:
                    def __init__(self):
                        self._lk = threading.Lock()
                    def export(self):
                        with self._lk:
                            pass
                    def evict(self, controller):
                        with self._lk:
                            with controller._lk:
                                pass
                """,
        })
        findings = run(str(tmp_path), rule_ids=["A6"])
        assert len(findings) == 1 and "cycle" in findings[0].message
        assert "autoscale.py:" in findings[0].message \
            and "warmstart.py:" in findings[0].message

    def test_a7_probe_under_decision_lock_trips(self, tmp_path):
        # the tempting bug: /health probes (urlopen) inside the decision
        # lock — one unresponsive replica freezes status() for everyone.
        # The shipped controller observes OUTSIDE the lock; this pins
        # the analyzer catching the inverse.
        write_tree(tmp_path, {
            "paddle_tpu/inference/autoscale.py":
                "import threading, urllib.request\n"
                "class Controller:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "    def tick(self):\n"
                "        with self._lk:\n"
                "            urllib.request.urlopen('http://x/health')\n",
        })
        findings = run(str(tmp_path), rule_ids=["A7"])
        assert len(findings) == 1 and findings[0].line == 7
        assert "urlopen" in findings[0].message

    def test_shipped_autoscale_and_warmstart_are_clean(self, tmp_path):
        # the real modules, verbatim, under all three passes: the
        # controller's decide-under-lock / actuate-outside-lock split is
        # load-bearing, not stylistic
        for rel in ("paddle_tpu/inference/autoscale.py",
                    "paddle_tpu/inference/warmstart.py"):
            dst = tmp_path / rel
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copyfile(os.path.join(REPO, rel), dst)
        assert run(str(tmp_path), rule_ids=["A5", "A6", "A7"]) == []


# --------------- fixtures: A5/A6/A7 on the request-lifecycle surface (19)

class TestLifecycleSurfaceInScope:
    """ISSUE 19: the cancel/hedge machinery makes the Router a
    lock-using, HTTP-touching concurrent class — exactly the surface
    A5/A6/A7 police. These fixtures plant each defect class at the
    literal new code paths (hedge bookkeeping RMW, cancel-vs-retire
    lock inversion, replica HTTP under the cancel-marks lock), plus
    the shipped files staying clean and the new chaos sites being
    registered AND test-named (rule A2)."""

    def test_a5_unlocked_hedge_token_bookkeeping_trips(self, tmp_path):
        # the one race budgeted hedging must not have: the token bucket
        # read-modify-written outside the lock double-spends under a
        # concurrent /cancel mark
        write_tree(tmp_path, {
            "paddle_tpu/inference/router.py":
                "import threading\n"
                "class Router:\n"
                "    def __init__(self):\n"
                "        self._cancel_lk = threading.Lock()\n"
                "        self._retry_tokens = 1.0\n"
                "    def _maybe_hedge(self):\n"
                "        with self._cancel_lk:\n"
                "            pass\n"
                "        self._retry_tokens -= 1.0\n",
        })
        findings = run(str(tmp_path), rule_ids=["A5"])
        assert len(findings) == 1 and findings[0].line == 9
        assert "read-modify-write" in findings[0].message

    def test_a6_cancel_vs_retire_inversion_trips(self, tmp_path):
        # router cancels INTO the replica while holding its cancel-marks
        # lock; the replica's retire path locks itself then reads the
        # router's marks — opposite orders across the two modules
        write_tree(tmp_path, {
            "paddle_tpu/inference/router.py": """\
                import threading
                class Router:
                    def __init__(self, rep):
                        self._cancel_lk = threading.Lock()
                        self._rep = rep
                    def cancel(self, rid):
                        with self._cancel_lk:
                            self._rep.cancel_local(rid)
                """,
            "paddle_tpu/inference/replica.py": """\
                import threading
                class ReplicaServer:
                    def __init__(self):
                        self._lk = threading.Lock()
                    def cancel_local(self, rid):
                        with self._lk:
                            pass
                    def retire(self, router):
                        with self._lk:
                            with router._cancel_lk:
                                pass
                """,
        })
        findings = run(str(tmp_path), rule_ids=["A6"])
        assert len(findings) == 1 and "cycle" in findings[0].message
        assert "router.py:" in findings[0].message \
            and "replica.py:" in findings[0].message

    def test_a7_replica_http_under_cancel_lock_trips(self, tmp_path):
        # the tempting bug the shipped _h_cancel/_apply_cancels split
        # exists to prevent: POSTing /cancel to a replica while holding
        # the marks lock — one blackholed replica wedges the admin
        # thread AND every tick's drain
        write_tree(tmp_path, {
            "paddle_tpu/inference/router.py":
                "import threading, urllib.request\n"
                "class Router:\n"
                "    def __init__(self):\n"
                "        self._cancel_lk = threading.Lock()\n"
                "    def _apply_cancels(self):\n"
                "        with self._cancel_lk:\n"
                "            urllib.request.urlopen('http://r0/cancel')\n",
        })
        findings = run(str(tmp_path), rule_ids=["A7"])
        assert len(findings) == 1 and findings[0].line == 7
        assert "urlopen" in findings[0].message

    def test_shipped_lifecycle_surface_is_clean(self, tmp_path):
        # the real modules, verbatim, under all three passes: the
        # decide-under-lock (mark) / actuate-outside (apply on the
        # router thread) split is load-bearing, not stylistic
        for rel in ("paddle_tpu/inference/router.py",
                    "paddle_tpu/inference/replica.py",
                    "paddle_tpu/inference/serving.py"):
            dst = tmp_path / rel
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copyfile(os.path.join(REPO, rel), dst)
        assert run(str(tmp_path), rule_ids=["A5", "A6", "A7"]) == []

    def test_a2_new_sites_registered_and_test_named(self):
        # request.cancel / router.hedge are registered with descriptions
        # and named literally by tests (test_reliability.py drives both);
        # an unregistered hit would be an A2 finding repo-wide
        from paddle_tpu.distributed.resilience import chaos as _chaos
        for site in ("request.cancel", "router.hedge"):
            assert site in _chaos.SITES and _chaos.SITES[site]
        src = open(os.path.join(HERE, "test_reliability.py")).read()
        assert "request.cancel:1" in src and "router.hedge:1+" in src


# --------------------------------------------- fixtures: A8 wire contract

_ROUTES_REG = """\
    IMPLIED_STATUSES = (403, 404, 500)
    ROUTES = {
        "/good": {"methods": ("GET",), "statuses": (200, 400),
                  "doc": "a documented route"},
        "/post_only": {"methods": ("POST",), "statuses": (200,),
                       "doc": "another one"},
    }
"""

_A8_SERVER = """\
    class Server:
        def __init__(self):
            self._admin = AdminServer(
                get_routes={"/good": self._h_good},
                post_routes={"/post_only": self._h_post})
        def _h_good(self, q):
            if q:
                return 400, {}
            return 200, {}
        def _h_post(self, body):
            return 200, {}
"""

_A8_CLIENT = """\
    class Client:
        def _get(self, endpoint, path):
            return 200, {}
        def _post(self, endpoint, path, obj):
            return 200, {}
        def poll(self, ep):
            code, _ = self._get(ep, "/good?x=1")
            if code == 400:
                return None
            self._post(ep, "/post_only", {})
"""

_A8_TESTS = "PATHS = ['/good', '/post_only']\n"


def _a8_tree(**overrides):
    files = {
        "paddle_tpu/inference/routes.py": _ROUTES_REG,
        "paddle_tpu/inference/server_x.py": _A8_SERVER,
        "paddle_tpu/inference/client_x.py": _A8_CLIENT,
        "tests/test_x.py": _A8_TESTS,
    }
    files.update(overrides)
    return files


class TestWireContractRegistry:
    def test_clean_fixture(self, tmp_path):
        write_tree(tmp_path, _a8_tree())
        assert run(str(tmp_path), rule_ids=["A8"]) == []

    def test_undeclared_registration(self, tmp_path):
        write_tree(tmp_path, _a8_tree(**{
            "paddle_tpu/inference/server_x.py": _A8_SERVER.replace(
                '"/good": self._h_good',
                '"/good": self._h_good, "/rogue": self._h_good')}))
        findings = run(str(tmp_path), rule_ids=["A8"])
        assert len(findings) == 1
        assert "'/rogue'" in findings[0].message
        assert "undeclared route" in findings[0].message

    def test_undeclared_client_route_and_method_mismatch(self, tmp_path):
        write_tree(tmp_path, _a8_tree(**{
            "paddle_tpu/inference/client_x.py": _A8_CLIENT.replace(
                'self._post(ep, "/post_only", {})',
                'self._post(ep, "/typo_route", {})\n'
                '        self._post(ep, "/good", {})')}))
        findings = run(str(tmp_path), rule_ids=["A8"])
        msgs = " | ".join(f.message for f in findings)
        assert "'/typo_route'" in msgs
        # /good declares GET only; the POST is the method-drift finding
        assert "sends POST to '/good'" in msgs
        # plus /post_only went dead (no client, no second registration
        # needed — the server still registers it, so NOT dead)
        assert "no registration" not in msgs

    def test_undeclared_handler_status(self, tmp_path):
        write_tree(tmp_path, _a8_tree(**{
            "paddle_tpu/inference/server_x.py": _A8_SERVER.replace(
                "return 400, {}", "return 418, {}")}))
        findings = run(str(tmp_path), rule_ids=["A8"])
        assert len(findings) == 1
        assert "418" in findings[0].message
        assert "_h_good" in findings[0].message

    def test_one_hop_status_through_helper(self, tmp_path):
        # return self._reject(...) counts the helper's 429 as the
        # handler's — the replica _reject_429 idiom
        server = _A8_SERVER.replace(
            "        def _h_post(self, body):\n"
            "            return 200, {}\n",
            "        def _h_post(self, body):\n"
            "            if body:\n"
            "                return self._reject()\n"
            "            return 200, {}\n"
            "        def _reject(self):\n"
            "            return 429, {}\n")
        write_tree(tmp_path, _a8_tree(**{
            "paddle_tpu/inference/server_x.py": server}))
        findings = run(str(tmp_path), rule_ids=["A8"])
        assert len(findings) == 1
        assert "429" in findings[0].message and "_h_post" in findings[0].message
        # declaring it clears the finding
        write_tree(tmp_path, {
            "paddle_tpu/inference/routes.py": _ROUTES_REG.replace(
                '"statuses": (200,),', '"statuses": (200, 429),')})
        assert run(str(tmp_path), rule_ids=["A8"]) == []

    def test_client_branch_on_impossible_status(self, tmp_path):
        write_tree(tmp_path, _a8_tree(**{
            "paddle_tpu/inference/client_x.py": _A8_CLIENT.replace(
                "if code == 400:", "if code == 402:")}))
        findings = run(str(tmp_path), rule_ids=["A8"])
        assert len(findings) == 1
        assert "402" in findings[0].message
        assert "no declared route can answer" in findings[0].message

    def test_transport_fault_sentinel_and_implied_are_fine(self, tmp_path):
        write_tree(tmp_path, _a8_tree(**{
            "paddle_tpu/inference/client_x.py": _A8_CLIENT.replace(
                "if code == 400:",
                "if code == 0 or code == 500 or code == 400:")}))
        assert run(str(tmp_path), rule_ids=["A8"]) == []

    def test_do_handler_literals_are_registrations(self, tmp_path):
        write_tree(tmp_path, _a8_tree(**{
            "paddle_tpu/inference/kvserver_x.py": """\
                class H:
                    def do_GET(self):
                        if self.path.startswith("/good/"):
                            return
                    def do_PUT(self):
                        if self.path == "/unplanned":
                            return
                """}))
        findings = run(str(tmp_path), rule_ids=["A8"])
        msgs = " | ".join(f.message for f in findings)
        # /good exists but declares GET only — do_GET matches; the PUT
        # route is undeclared entirely
        assert "'/unplanned'" in msgs
        assert len(findings) == 1

    def test_route_unnamed_by_any_test(self, tmp_path):
        write_tree(tmp_path, _a8_tree(**{
            "tests/test_x.py": "PATHS = ['/good']\n"}))
        findings = run(str(tmp_path), rule_ids=["A8"])
        assert len(findings) == 1
        assert "'/post_only'" in findings[0].message
        assert "named by no test" in findings[0].message
        # substring safety: naming "/good" must not satisfy "/goo"

    def test_dead_declaration(self, tmp_path):
        write_tree(tmp_path, _a8_tree(**{
            "paddle_tpu/inference/routes.py": _ROUTES_REG.replace(
                "    }",
                '    "/never_wired": {"methods": ("GET",),\n'
                '                     "statuses": (200,), "doc": "dead"},\n'
                "    }"),
            "tests/test_x.py":
                "PATHS = ['/good', '/post_only', '/never_wired']\n"}))
        findings = run(str(tmp_path), rule_ids=["A8"])
        assert len(findings) == 1
        assert "'/never_wired'" in findings[0].message
        assert "no registration and no client call site" in \
            findings[0].message

    def test_missing_registry_reported_once(self, tmp_path):
        files = _a8_tree()
        del files["paddle_tpu/inference/routes.py"]
        write_tree(tmp_path, files)
        findings = run(str(tmp_path), rule_ids=["A8"])
        assert len(findings) == 1
        assert "no parseable ROUTES registry" in findings[0].message

    def test_registry_hygiene_duplicate_and_docless(self, tmp_path):
        write_tree(tmp_path, _a8_tree(**{
            "paddle_tpu/inference/routes.py": _ROUTES_REG.replace(
                "    }",
                '    "/good": {"methods": ("GET",), "statuses": (200,),\n'
                '              "doc": "duplicate"},\n'
                '    "/bare": {"methods": ("GET",), "statuses": (200,),\n'
                '              "doc": ""},\n'
                "    }")}))
        findings = run(str(tmp_path), rule_ids=["A8"])
        msgs = " | ".join(f.message for f in findings)
        assert "duplicate route '/good'" in msgs
        assert "without a doc" in msgs

    def test_changed_scope_cannot_fabricate_or_miss(self, tmp_path):
        # registries are global under --changed: a walk restricted to the
        # CLIENT file must neither invent findings (the registry and
        # server it never visited still count) nor miss the typo finding
        write_tree(tmp_path, _a8_tree(**{
            "paddle_tpu/inference/client_x.py": _A8_CLIENT.replace(
                '"/good?x=1"', '"/typo_route?x=1"')}))
        full = run(str(tmp_path), rule_ids=["A8"])
        partial = run(str(tmp_path), rule_ids=["A8"],
                      files=["paddle_tpu/inference/client_x.py"])
        assert [f.message for f in partial] == [f.message for f in full]
        assert len(full) == 1 and "'/typo_route'" in full[0].message

    def test_marker_suppresses_call_site(self, tmp_path):
        write_tree(tmp_path, _a8_tree(**{
            "paddle_tpu/inference/client_x.py": _A8_CLIENT.replace(
                'self._post(ep, "/post_only", {})',
                'self._post(ep, "/post_only", {})\n'
                '        self._get(ep, "/external_svc")'
                '  # wire: ok (third-party sidecar endpoint, not ours)')}))
        assert run(str(tmp_path), rule_ids=["A8"]) == []


# ------------------------------------------------------ driver contract

class TestDriver:
    def test_whole_repo_exits_zero_against_committed_baseline(self):
        # ONE full-repo CLI run covers both acceptance contracts: exit 0
        # with zero live findings, and zero stale baseline entries (the
        # baseline only ever shrinks)
        r = analyze_cli(REPO, "--json")
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(r.stdout)
        assert doc["counts"]["live"] == 0
        assert doc["stale_baseline"] == []

    def test_json_report_schema(self, tmp_path, capsys):
        root = write_tree(tmp_path, {
            "paddle_tpu/inference/bad.py":
                "import time\nt = time.perf_counter()\n"})
        rc, out = analyze_run(root, "--rules", "O4", "--json",
                              capsys=capsys)
        assert rc == 1
        doc = json.loads(out)
        assert doc["counts"]["live"] == 1
        f = doc["findings"][0]
        assert f["rule"] == "O4" and f["path"] == "paddle_tpu/inference/bad.py"
        assert set(f) == {"rule", "path", "line", "message"}

    def test_rules_subset_filters(self, tmp_path, capsys):
        root = write_tree(tmp_path, {
            "paddle_tpu/inference/bad.py":
                "import time\nt = time.perf_counter()\n"})
        assert analyze_run(root, "--rules", "A1,A5", capsys=capsys)[0] == 0
        assert analyze_run(root, "--rules", "O4", capsys=capsys)[0] == 1

    def test_baseline_suppresses_and_requires_reason(self, tmp_path, capsys):
        root = write_tree(tmp_path, {
            "paddle_tpu/inference/bad.py":
                "import time\nt = time.perf_counter()\n"})
        bl = tmp_path / "BL.json"
        bl.write_text(json.dumps({"entries": [{
            "rule": "O4", "path": "paddle_tpu/inference/bad.py",
            "code": "t = time.perf_counter()",
            "reason": "fixture: grandfathered for the suppression test"}]}))
        rc, out = analyze_run(root, "--baseline", str(bl), capsys=capsys)
        assert rc == 0 and "baselined" in out
        bl.write_text(json.dumps({"entries": [{
            "rule": "O4", "path": "paddle_tpu/inference/bad.py",
            "code": "t = time.perf_counter()", "reason": ""}]}))
        assert analyze_run(root, "--baseline", str(bl),
                           capsys=capsys)[0] == 2

    def test_fix_markers_lists_stale_entries(self, tmp_path, capsys):
        root = write_tree(tmp_path, {"paddle_tpu/clean.py": "x = 1\n"})
        bl = tmp_path / "BL.json"
        bl.write_text(json.dumps({"entries": [{
            "rule": "O4", "path": "paddle_tpu/gone.py",
            "code": "t = time.perf_counter()",
            "reason": "the finding this covered was fixed"}]}))
        rc, out = analyze_run(root, "--baseline", str(bl), "--fix-markers",
                              capsys=capsys)
        assert rc == 1
        assert "no longer reproduce" in out
        assert "paddle_tpu/gone.py" in out

    def test_baseline_entries_are_one_shot(self, tmp_path, capsys):
        # one grandfathered entry must NOT absorb a freshly pasted COPY of
        # the same offending line — the second occurrence stays live
        root = write_tree(tmp_path, {
            "paddle_tpu/inference/bad.py":
                "import time\n"
                "t = time.perf_counter()\n"
                "u = time.perf_counter()\n"})
        bl = tmp_path / "BL.json"
        bl.write_text(json.dumps({"entries": [
            {"rule": "O4", "path": "paddle_tpu/inference/bad.py",
             "code": "t = time.perf_counter()",
             "reason": "fixture: the original grandfathered line"}]}))
        rc, out = analyze_run(root, "--baseline", str(bl), capsys=capsys)
        assert rc == 1  # line 3 is live; only line 2 rides the entry
        assert "1 baselined" in out

    def test_changed_mode_never_reports_unvisited_entries_stale(
            self, tmp_path, capsys, monkeypatch):
        # a diff-scoped pass skips unchanged files; their baseline entries
        # must not be called stale (deleting them would break the full run)
        root = write_tree(tmp_path, {
            "paddle_tpu/inference/grandfathered.py":
                "import time\nt = time.perf_counter()\n",
            "paddle_tpu/touched.py": "x = 1\n"})
        bl = tmp_path / "BL.json"
        bl.write_text(json.dumps({"entries": [
            {"rule": "O4", "path": "paddle_tpu/inference/grandfathered.py",
             "code": "t = time.perf_counter()",
             "reason": "fixture: lives in an UNCHANGED file"}]}))
        import tools.analyze.__main__ as m
        monkeypatch.setattr(m, "changed_files",
                            lambda _root: ["paddle_tpu/touched.py"])
        rc, out = analyze_run(root, "--changed", "--baseline", str(bl),
                              capsys=capsys)
        assert rc == 0 and "stale" not in out
        # and --fix-markers ignores --changed: the full-scope pass sees the
        # entry still reproduces, so nothing is listed for deletion
        rc, out = analyze_run(root, "--changed", "--fix-markers",
                              "--baseline", str(bl), capsys=capsys)
        assert rc == 0 and "still reproduce" in out

    @pytest.mark.skipif(shutil.which("git") is None, reason="needs git")
    def test_changed_mode_scopes_to_diff(self, tmp_path, capsys):
        root = write_tree(tmp_path, {
            "paddle_tpu/clean.py": "x = 1\n",
            "paddle_tpu/other.py": "import time\nt = time.perf_counter()\n",
        })
        env = {**os.environ, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
               "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}
        for cmd in (["git", "init", "-q"], ["git", "add", "-A"],
                    ["git", "commit", "-qm", "seed"]):
            subprocess.run(cmd, cwd=root, env=env, check=True,
                           capture_output=True)
        rc, out = analyze_run(root, "--changed", capsys=capsys)
        assert rc == 0 and "no changed" in out
        # introduce an O1 finding in a changed file
        (tmp_path / "paddle_tpu/clean.py").write_text("print('boom')\n")
        rc, out = analyze_run(root, "--changed", capsys=capsys)
        assert rc == 1 and "[O1]" in out
        assert "clean.py" in out

    def test_json_and_exit_flip_for_new_rules(self, tmp_path, capsys):
        # A6/A7/A8 ride the same driver contract: --json schema, exit 1
        root = write_tree(tmp_path, {
            "paddle_tpu/inference/bad.py":
                "import threading, time\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "    def f(self):\n"
                "        with self._lk:\n"
                "            time.sleep(0.1)\n"})
        rc, out = analyze_run(root, "--rules", "A7", "--json",
                              capsys=capsys)
        assert rc == 1
        doc = json.loads(out)
        assert doc["counts"]["live"] == 1
        assert doc["findings"][0]["rule"] == "A7"
        # fixing it flips the driver back to 0
        (tmp_path / "paddle_tpu/inference/bad.py").write_text(
            textwrap.dedent("""\
                import threading, time
                class C:
                    def __init__(self):
                        self._lk = threading.Lock()
                    def f(self):
                        with self._lk:
                            pass
                        time.sleep(0.1)
                """))
        assert analyze_run(root, "--rules", "A7", capsys=capsys)[0] == 0

    def test_stats_reports_per_rule_seconds(self, tmp_path, capsys):
        root = write_tree(tmp_path, {"paddle_tpu/clean.py": "x = 1\n"})
        rc = analyze_main([root, "--stats"])
        err = capsys.readouterr().err
        assert rc == 0
        assert "per-rule wall seconds" in err
        for rid in ("A6", "A7", "A8"):
            assert rid in err

    def test_committed_baseline_passes_the_reason_gate(self):
        # the satellite contract: the committed baseline parses, carries
        # no reasonless entries (driver would exit 2), and has nothing
        # stale (--fix-markers exits 0: the file only ever shrinks)
        from tools.analyze.core import BASELINE_NAME, load_baseline
        bl = load_baseline(os.path.join(REPO, BASELINE_NAME))
        assert bl.errors() == []
        r = analyze_cli(REPO, "--fix-markers")
        assert r.returncode == 0, r.stdout + r.stderr

    def test_shims_restricted_to_their_families(self, tmp_path, capsys):
        # an A5 race trips the unified driver but NOT the legacy shims
        root = write_tree(tmp_path, {
            "paddle_tpu/observability/bad.py":
                "import threading\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._lk = threading.Lock()\n"
                "        self.n = 0\n"
                "    def f(self):\n"
                "        with self._lk:\n"
                "            pass\n"
                "        self.n += 1\n",
        })
        assert analyze_run(root, capsys=capsys)[0] == 1
        for shim in ("lint_resilience.py", "lint_observability.py"):
            r = subprocess.run(
                [sys.executable, os.path.join(REPO, "tools", shim), root],
                capture_output=True, text=True, timeout=120)
            assert r.returncode == 0, (shim, r.stdout)


# ------------------------------------------------- runtime registry mirrors

class TestChaosRuntimeMirror:
    def test_unregistered_site_warns_and_records_once(self):
        from paddle_tpu.distributed.resilience import chaos
        from paddle_tpu.observability import recorder
        with chaos.inject("unrelated.site:1"):
            before = recorder.events_since(0)[1]
            assert chaos.hit("never.registered") == 1  # no raise
            assert chaos.hit("never.registered") == 2
            evs = [e for e in recorder.events_since(before)[0]
                   if e.get("kind") == "chaos.unregistered_site"]
            assert len(evs) == 1
            assert evs[0]["site"] == "never.registered"

    def test_registered_site_records_nothing_extra(self):
        from paddle_tpu.distributed.resilience import chaos
        from paddle_tpu.observability import recorder
        with chaos.inject("unrelated.site:1"):
            before = recorder.events_since(0)[1]
            chaos.hit("serve.burst")
            evs = [e for e in recorder.events_since(before)[0]
                   if e.get("kind") == "chaos.unregistered_site"]
            assert evs == []

    def test_no_chaos_env_is_still_a_noop(self, monkeypatch):
        from paddle_tpu.distributed.resilience import chaos
        monkeypatch.delenv("PADDLE_CHAOS", raising=False)
        assert chaos.hit("never.registered") == 0

    def test_every_registered_site_has_a_live_call_site(self):
        # SITES is ground truth for the tree: every entry matches a literal
        # chaos.hit("<site>") somewhere (the A2 unused direction)
        from paddle_tpu.distributed.resilience import chaos
        import subprocess as sp
        src = sp.run(["grep", "-rn", "--include=*.py", "-e", "hit(",
                      os.path.join(REPO, "paddle_tpu")],
                     capture_output=True, text=True).stdout
        for site in chaos.SITES:
            assert f'"{site}"' in src or f"'{site}'" in src, \
                f"registered chaos site {site!r} has no hit() call site"


# --------------------------------------------- race-fix regression tests

class TestLockRaceRegressions:
    """The two real findings the A5 pass surfaced on the ISSUE-7 tree,
    fixed in this PR — pinned so they stay fixed."""

    def test_slo_breached_count_exact_under_concurrency(self):
        from paddle_tpu.observability import slo
        tracker = slo.RequestTracker(policy=slo.SloPolicy(e2e_s=1e-12))
        n_threads, per_thread = 8, 50
        total = n_threads * per_thread
        for rid in range(total):
            tracker.on_enqueue(rid)
        start = threading.Barrier(n_threads)

        def retire(block):
            start.wait()
            for rid in block:
                tracker.on_retire(rid, n_tokens=0)

        threads = [threading.Thread(target=retire, args=(
            range(i * per_thread, (i + 1) * per_thread),))
            for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # pre-fix: `self.breached += 1` ran outside the tracker lock and
        # lost updates under contention; the count must be EXACT
        assert tracker.breached == total

    def test_fleet_command_offset_reads_each_line_once(self, tmp_path):
        from paddle_tpu.observability import fleet
        client = fleet.TelemetryClient(directory=str(tmp_path),
                                       node="n0", rank=0)
        n_cmds = 600
        cmd_file = tmp_path / "cmd.n0.0.jsonl"
        cmd_file.write_text("".join(
            json.dumps({"cmd": "xplane", "steps": 1, "i": i}) + "\n"
            for i in range(n_cmds)))
        n_threads = 8
        start = threading.Barrier(n_threads)
        got: list[list] = [[] for _ in range(n_threads)]

        def reader(slot):
            start.wait()
            for _ in range(50):
                got[slot].extend(client._read_dir_commands())

        threads = [threading.Thread(target=reader, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        seen = [c["i"] for block in got for c in block]
        # pre-fix: the unlocked `self._cmd_off +=` let two readers start at
        # the same offset and deliver (and apply) the same command twice
        assert sorted(seen) == list(range(n_cmds))

    # (the whole-repo A5 cleanliness assertion rides the shared pass in
    # TestTelemetryNameRegistry.
    # test_repo_names_clean_and_standard_declarations_parsed)


# ------------------------------------------------------- pre-commit wiring

class TestPreCommitWiring:
    """ROADMAP tooling item (closed, ISSUE 8): `python -m tools.analyze
    --changed` is wired into a COMMITTED pre-commit config, and that exact
    hook command exits clean on the repo itself — findings land before the
    suite runs, and the config cannot silently drift from the CLI."""

    CONFIG = os.path.join(REPO, ".pre-commit-config.yaml")

    def test_committed_config_wires_the_changed_pass(self):
        assert os.path.exists(self.CONFIG), \
            ".pre-commit-config.yaml must be committed at the repo root"
        src = open(self.CONFIG).read()
        # string-contract asserts (no yaml dep in the container): the hook
        # is the diff-scoped analyzer, run as-is against this interpreter
        assert "python -m tools.analyze --changed" in src
        assert "language: system" in src
        assert "pass_filenames: false" in src
        assert "id: paddle-analyze" in src

    def test_hook_rule_set_covers_the_new_passes(self, capsys):
        # the --changed hook runs EVERY registered rule; --list is the
        # user-facing catalog and must show the ISSUE-15 passes
        rc = analyze_main(["--list"])
        out = capsys.readouterr().out
        assert rc == 0
        for rid, title in (("A6", "lock-order"),
                           ("A7", "blocking-under-lock"),
                           ("A8", "wire-contract-registry")):
            assert rid in out and title in out

    def test_hook_command_is_clean_on_the_repo(self):
        """Run the exact committed hook entry (fresh interpreter, repo
        root): a dirty working tree must analyze clean, else every commit
        in this repo would be blocked."""
        entry = next(ln.split("entry:", 1)[1].strip()
                     for ln in open(self.CONFIG)
                     if ln.strip().startswith("entry:"))
        assert entry.startswith("python -m tools.analyze")
        r = subprocess.run([sys.executable, *entry.split()[1:]],
                           capture_output=True, text=True, cwd=REPO,
                           timeout=180)
        assert r.returncode == 0, r.stdout + r.stderr


class TestAnalyzerPerfGuard:
    """ISSUE 15 satellite: the whole-repo analyzer wall is pinned under a
    budget so new cross-file passes cannot silently regress the tier-1
    wall the way PR 7 had to profile down after the fact (the ROADMAP's
    verify-timeout history is load-bearing). Measured wall on this tree:
    ~1.5s in-process; the 30s budget is machine-load headroom, not an
    invitation."""

    BUDGET_S = 30.0

    def test_whole_repo_wall_under_budget(self):
        import time as _time
        t0 = _time.perf_counter()
        r = analyze_cli(REPO)
        wall = _time.perf_counter() - t0
        assert r.returncode == 0, r.stdout + r.stderr
        assert wall < self.BUDGET_S, (
            f"whole-repo analyze took {wall:.1f}s (budget "
            f"{self.BUDGET_S}s) — profile the new passes with "
            "`python -m tools.analyze --stats` before raising this")
