"""fmda_tpu.analysis: engine, rule fixtures, baseline, CLI (ISSUE 8).

Layout mirrors the acceptance criteria: every analyzer gets a
true-positive/true-negative fixture pair, the baseline suppression
round-trips, the ``--json`` schema is pinned, and ONE test runs the
whole suite against the shipped baseline — the tier-1 gate every future
PR lands under.
"""

import json
import pathlib

import pytest

import fmda_tpu
from fmda_tpu.analysis import (
    BusTopicRule,
    ChaosGuardRule,
    CompatRequiredRule,
    CountedLossRule,
    Finding,
    JaxApiDriftRule,
    JitPurityRule,
    LintContext,
    LintResult,
    LockDisciplineRule,
    LoggingHygieneRule,
    ParsedModule,
    SpanClockRule,
    ThreadLifecycleRule,
    WireProtocolRule,
    apply_baseline,
    collect_modules,
    default_rules,
    load_baseline,
    run_lint,
    run_rules,
    save_baseline,
    to_sarif,
)

PACKAGE_DIR = pathlib.Path(fmda_tpu.__file__).parent


def run_on(rule, sources, package_dir=PACKAGE_DIR):
    """Run one rule over ``{rel: source}`` fixture modules."""
    modules = [ParsedModule.from_source(src, rel)
               for rel, src in sources.items()]
    ctx = LintContext(package_dir, modules)
    findings, suppressed = run_rules([rule], ctx)
    return findings, suppressed, ctx


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def test_parsed_module_comment_map_ignores_strings():
    m = ParsedModule.from_source(
        's = "# not a comment"\nx = 1  # real comment\n')
    assert m.comments == {2: "real comment"}


def test_finding_key_is_line_free():
    a = Finding("r", "p.py", 10, "msg")
    b = Finding("r", "p.py", 99, "msg")
    assert a.key == b.key
    assert set(a.as_dict()) == {"rule", "path", "line", "severity",
                                "message"}


def test_generic_ignore_hatch_requires_a_reason():
    src_with = ("import threading\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "        self.n = 0\n"
                "    def bump(self):\n"
                "        with self._lock:\n"
                "            self.n += 1\n"
                "    def peek(self):\n"
                "        return self.n  "
                "# lint: ignore[lock-discipline] scrape-time skew is fine\n")
    findings, suppressed, _ = run_on(
        LockDisciplineRule(), {"mod.py": src_with})
    assert not findings and suppressed == 1
    src_bare = src_with.replace(" scrape-time skew is fine", "")
    findings, suppressed, _ = run_on(
        LockDisciplineRule(), {"mod.py": src_bare})
    assert len(findings) == 1 and suppressed == 0  # reasonless = inert


# ---------------------------------------------------------------------------
# Lock discipline
# ---------------------------------------------------------------------------

LOCK_TP = """\
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0

    def bump(self):
        with self._lock:
            self.n += 1

    def peek(self):
        return self.n
"""


def test_lock_rule_flags_unguarded_read():
    findings, _, _ = run_on(LockDisciplineRule(), {"mod.py": LOCK_TP})
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "lock-discipline"
    assert "C.peek" in f.message and "self.n" in f.message


def test_lock_rule_clean_when_guarded():
    src = LOCK_TP.replace(
        "    def peek(self):\n        return self.n\n",
        "    def peek(self):\n        with self._lock:\n"
        "            return self.n\n")
    findings, _, _ = run_on(LockDisciplineRule(), {"mod.py": src})
    assert not findings


def test_lock_rule_guarded_by_annotation_alone():
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.state = {}  # guarded-by: _lock\n"
           "    def read(self):\n"
           "        return self.state\n")
    findings, _, _ = run_on(LockDisciplineRule(), {"mod.py": src})
    assert len(findings) == 1 and "self.state" in findings[0].message


def test_lock_rule_lock_free_hatch():
    src = LOCK_TP.replace(
        "        return self.n",
        "        # lock-free: GIL-atomic int read, skew tolerated\n"
        "        return self.n")
    findings, _, _ = run_on(LockDisciplineRule(), {"mod.py": src})
    assert not findings


def test_lock_rule_locked_suffix_contract():
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.n = 0\n"
           "    def _peek_locked(self):\n"
           "        return self.n\n"
           "    def good(self):\n"
           "        with self._lock:\n"
           "            self.n += 1\n"
           "            return self._peek_locked()\n"
           "    def bad(self):\n"
           "        return self._peek_locked()\n")
    findings, _, _ = run_on(LockDisciplineRule(), {"mod.py": src})
    assert len(findings) == 1
    assert "C.bad" in findings[0].message
    assert "_peek_locked" in findings[0].message


def test_lock_rule_infers_guarded_from_container_mutation():
    # the repo's dominant shape: shared dicts/deques mutated in place
    # under the lock, never rebound — the inference must see
    # subscript stores and mutator-method calls, not just `self.x = ...`
    src = ("import threading\n"
           "class Bus:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self._logs = {}\n"
           "    def publish(self, topic, rec):\n"
           "        with self._lock:\n"
           "            self._logs[topic].append(rec)\n"
           "    def read(self, topic):\n"
           "        return list(self._logs[topic])\n")
    findings, _, _ = run_on(LockDisciplineRule(), {"mod.py": src})
    assert len(findings) == 1
    assert "Bus.read" in findings[0].message
    assert "self._logs" in findings[0].message


def test_lock_rule_init_exempt_and_lockless_class_skipped():
    src = ("class NoLock:\n"
           "    def __init__(self):\n"
           "        self.n = 0\n"
           "    def bump(self):\n"
           "        self.n += 1\n")
    findings, _, _ = run_on(LockDisciplineRule(), {"mod.py": src})
    assert not findings


# ---------------------------------------------------------------------------
# Jit purity
# ---------------------------------------------------------------------------


def test_purity_flags_wall_clock_in_decorated_jit():
    src = ("import time\n"
           "import jax\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    t = time.time()\n"
           "    return x + t\n")
    findings, _, _ = run_on(JitPurityRule(), {"mod.py": src})
    assert any("wall-clock" in f.message for f in findings)


def test_purity_transitive_one_level():
    src = ("import jax\n"
           "def helper(x):\n"
           "    print(x)\n"
           "    return x\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return helper(x)\n")
    findings, _, _ = run_on(JitPurityRule(), {"mod.py": src})
    assert any("print" in f.message and "helper" in f.message
               for f in findings)


def test_purity_host_method_sharing_a_jitted_closure_name_is_clean():
    # the repo's streaming-core shape: `step` the host method calls
    # `self._step`, the jitted closure ALSO named `step` — Python
    # scoping must keep the host method out of the jit-reachable set
    src = ("import jax\n"
           "import numpy as np\n"
           "class Core:\n"
           "    def __init__(self):\n"
           "        def step(carry, row):\n"
           "            return carry + row\n"
           "        self._step = jax.jit(step)\n"
           "    def step(self, row):\n"
           "        self.count = 1\n"
           "        out = self._step(self.carry, row)\n"
           "        return np.asarray(out)\n")
    findings, _, _ = run_on(JitPurityRule(), {"mod.py": src})
    assert not findings


def test_purity_flags_self_mutation_and_host_rng():
    src = ("import jax\n"
           "import random\n"
           "class M:\n"
           "    def build(self):\n"
           "        def step(x):\n"
           "            self.cache = x\n"
           "            return x * random.random()\n"
           "        return jax.jit(step)\n")
    findings, _, _ = run_on(JitPurityRule(), {"mod.py": src})
    msgs = "\n".join(f.message for f in findings)
    assert "mutates self.cache" in msgs
    assert "host RNG" in msgs


def test_purity_donation_use_after_donate():
    src = ("import jax\n"
           "def train(fn, state, batch):\n"
           "    step = jax.jit(fn, donate_argnums=(0,))\n"
           "    out = step(state, batch)\n"
           "    return out, state\n")
    findings, _, _ = run_on(JitPurityRule(), {"mod.py": src})
    assert any("donated" in f.message and "'state'" in f.message
               for f in findings)


def test_purity_donation_rebind_is_clean():
    src = ("import jax\n"
           "def train(fn, state, batch):\n"
           "    step = jax.jit(fn, donate_argnums=(0,))\n"
           "    state = step(state, batch)\n"
           "    return state\n")
    findings, _, _ = run_on(JitPurityRule(), {"mod.py": src})
    assert not findings


# ---------------------------------------------------------------------------
# JAX API drift
# ---------------------------------------------------------------------------


def test_drift_flags_missing_symbol_in_scope():
    src = ("import jax\n"
           "x = jax.numpy.definitely_not_an_api_zz\n")
    findings, _, _ = run_on(JaxApiDriftRule(), {"ops/fake.py": src})
    assert len(findings) == 1
    assert "jax.numpy.definitely_not_an_api_zz" in findings[0].message
    assert findings[0].severity == "error"


def test_drift_resolves_aliases_and_skips_out_of_scope():
    good = ("import jax\n"
            "import jax.numpy as jnp\n"
            "from jax import lax\n"
            "y = jnp.ones\n"
            "z = lax.scan\n"
            "w = jax.tree_util.tree_map\n")
    findings, _, _ = run_on(JaxApiDriftRule(), {"ops/fake.py": good})
    assert not findings
    bad_but_out_of_scope = ("import jax\n"
                            "x = jax.numpy.definitely_not_an_api_zz\n")
    findings, _, _ = run_on(
        JaxApiDriftRule(), {"stream/fake.py": bad_but_out_of_scope})
    assert not findings


def test_drift_report_inventory_shape():
    src = ("import jax\n"
           "a = jax.numpy.definitely_not_an_api_zz\n"
           "b = jax.numpy.definitely_not_an_api_zz\n")
    _, _, ctx = run_on(JaxApiDriftRule(), {"parallel/fake.py": src})
    rep = ctx.reports["jax_api_drift"]
    assert rep["n_symbols"] == 1
    sites = rep["symbols"]["jax.numpy.definitely_not_an_api_zz"]
    assert [s["line"] for s in sites] == [2, 3]
    assert rep["jax_version"]


def test_drift_rule_is_zero_baseline(tmp_path):
    """The drift rule admits NO grandfathering: its findings stay new
    even when a matching baseline entry exists, and the entry itself is
    reported as forbidden debt that fails the gate."""
    src = ("import jax\n"
           "x = jax.numpy.definitely_not_an_api_zz\n")
    modules = [ParsedModule.from_source(src, "ops/fake.py")]
    ctx = LintContext(PACKAGE_DIR, modules)
    path = tmp_path / "baseline.json"
    save_baseline(
        [{"rule": "jax-api-drift", "path": "ops/fake.py",
          "message": ("unresolved jax reference: "
                      "jax.numpy.definitely_not_an_api_zz"),
          "justification": "trying to grandfather drift"}],
        path)
    result = run_lint([JaxApiDriftRule()], ctx=ctx, baseline_path=path)
    assert not result.ok
    assert len(result.new) == 1  # NOT matched away by the entry
    assert not result.baselined
    assert [e["rule"] for e in result.forbidden_baseline] == ["jax-api-drift"]


def test_drift_rule_ignores_the_inline_hatch_too():
    # a hard gate with an escape hatch is a soft gate: the generic
    # `# lint: ignore[jax-api-drift] reason` hatch must NOT suppress
    # drift findings (it keeps working for grandfatherable rules)
    src = ("import jax\n"
           "x = jax.numpy.definitely_not_an_api_zz"
           "  # lint: ignore[jax-api-drift] dodge the gate\n")
    findings, suppressed, _ = run_on(JaxApiDriftRule(), {"ops/fake.py": src})
    assert len(findings) == 1 and suppressed == 0


# ---------------------------------------------------------------------------
# compat-required: version-sensitive spellings stay in compat.py
# ---------------------------------------------------------------------------


def test_compat_rule_flags_direct_shimmed_symbol():
    # every arbitrated spelling, old and new, through both import styles
    src = ("import jax\n"
           "from jax.experimental.pallas import tpu as pltpu\n"
           "from jax.experimental.shard_map import shard_map\n"
           "a = pltpu.TPUCompilerParams(dimension_semantics=())\n"
           "b = pltpu.CompilerParams\n"
           "c = jax.lax.axis_size('sp')\n"
           "d = jax.lax.pcast\n"
           "e = jax.shard_map\n")
    findings, _, _ = run_on(CompatRequiredRule(), {"parallel/fake.py": src})
    flagged = {f.message.split(": ", 1)[1].split(" —")[0] for f in findings}
    assert flagged == {
        "jax.experimental.pallas.tpu.TPUCompilerParams",
        "jax.experimental.pallas.tpu.CompilerParams",
        "jax.experimental.shard_map.shard_map",
        "jax.lax.axis_size",
        "jax.lax.pcast",
        "jax.shard_map",
    }
    assert all(f.severity == "error" for f in findings)
    assert all("fmda_tpu.compat" in f.message for f in findings)


def test_compat_rule_clean_paths():
    # the sanctioned shape: shim imports + untouched jax APIs; and the
    # same direct use OUTSIDE the kernel surface is none of this rule's
    # business (compat.py itself lives at the package root, out of scope)
    good = ("import jax\n"
            "from fmda_tpu.compat import CompilerParams, axis_size\n"
            "n = axis_size('sp')\n"
            "y = jax.lax.psum(1, 'sp')\n"
            "z = jax.numpy.ones\n")
    findings, _, _ = run_on(CompatRequiredRule(), {"ops/fake.py": good})
    assert not findings
    out_of_scope = ("import jax\n"
                    "e = jax.shard_map\n")
    findings, _, _ = run_on(
        CompatRequiredRule(), {"stream/fake.py": out_of_scope})
    assert not findings


def test_compat_rule_catches_chains_past_the_symbol():
    src = ("import jax\n"
           "doc = jax.lax.axis_size.__doc__\n")
    findings, _, _ = run_on(CompatRequiredRule(), {"models/fake.py": src})
    assert len(findings) == 1 and "jax.lax.axis_size" in findings[0].message


def test_compat_shims_resolve_against_installed_jax():
    """Every shim must produce a working object on THIS jax — the whole
    point of probing at import is that either spelling works."""
    from fmda_tpu import compat

    assert compat.CompilerParams(dimension_semantics=("arbitrary",))
    assert callable(compat.shard_map)
    assert callable(compat.pcast)
    assert callable(compat.axis_size)
    # the symbol list and the shims stay in sync
    assert set(compat.SHIMMED_SYMBOLS.values()) <= set(compat.__all__)


def test_compat_module_imports_jax_free():
    """compat must stay importable (and SHIMMED_SYMBOLS readable) without
    jax — the analyzer runs on jax-free hosts."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from fmda_tpu.compat import SHIMMED_SYMBOLS\n"
            "assert 'jax' not in sys.modules, 'compat imported jax eagerly'\n"
            "assert SHIMMED_SYMBOLS\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True,
                          cwd=str(PACKAGE_DIR.parent))
    assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# Bus topics
# ---------------------------------------------------------------------------

TOPIC_CONFIG = ('TOPIC_A = "alpha"\n'
                'TOPIC_FLEET_TICKS_PREFIX = "fleet_ticks_"\n')


def test_topics_flags_published_but_never_declared():
    src = ('def go(bus):\n'
           '    bus.publish("typo_topic", {})\n')
    findings, _, _ = run_on(
        BusTopicRule(), {"config.py": TOPIC_CONFIG, "mod.py": src})
    assert len(findings) == 1
    assert "'typo_topic'" in findings[0].message


def test_topics_clean_paths():
    src = ('from fmda_tpu.config import TOPIC_A, TOPIC_FLEET_TICKS_PREFIX\n'
           'def go(bus, wid):\n'
           '    bus.publish("alpha", {})\n'          # config literal
           '    bus.publish(TOPIC_A, {})\n'          # config constant
           '    bus.publish(TOPIC_FLEET_TICKS_PREFIX + wid, {})\n'  # prefix
           '    bus.publish_many("beta", [])\n'      # consumed elsewhere
           '    bus.publish(wid, {})\n')             # dynamic: skipped
    other = ('def listen(bus):\n'
             '    bus.consumer("beta")\n')
    findings, _, ctx = run_on(
        BusTopicRule(),
        {"config.py": TOPIC_CONFIG, "mod.py": src, "other.py": other})
    assert not findings
    assert ctx.reports["bus_topics"]["declared"] == ["alpha"]


# ---------------------------------------------------------------------------
# Hygiene rules (fixture-level; repo-level runs live in
# tests/test_logging_hygiene.py)
# ---------------------------------------------------------------------------


def test_hot_path_json_rule_fixture_pair():
    from fmda_tpu.analysis import HotPathJsonRule

    bad = ("import json\n"
           "def f(v):\n"
           "    return json.dumps(v)\n")
    findings, _, _ = run_on(HotPathJsonRule(), {"fleet/x.py": bad})
    assert len(findings) == 1 and "json.dumps" in findings[0].message
    # alias-aware both ways
    aliased = ("import json as j\n"
               "from json import loads as parse\n"
               "def f(b):\n"
               "    return j.dumps(parse(b))\n")
    findings, _, _ = run_on(HotPathJsonRule(), {"runtime/x.py": aliased})
    assert len(findings) == 2
    # the codec module is the sanctioned home
    findings, _, _ = run_on(HotPathJsonRule(), {"stream/codec.py": bad})
    assert not findings
    # out of scope: the control plane may speak json freely
    findings, _, _ = run_on(HotPathJsonRule(), {"obs/events.py": bad})
    assert not findings
    # the in-place hatch sanctions a named control-plane site
    hatched = ("import json\n"
               "def f(v):\n"
               "    # lint: ignore[hot-path-json] checkpoint metadata, not per-tick\n"
               "    return json.dumps(v)\n")
    findings, suppressed, _ = run_on(
        HotPathJsonRule(), {"fleet/x.py": hatched})
    assert not findings and suppressed == 1


def test_hot_path_json_scope_lists_police_staleness(tmp_path):
    from fmda_tpu.analysis import HotPathJsonRule

    findings, _, _ = run_on(
        HotPathJsonRule(), {"fleet/x.py": "x = 1\n"},
        package_dir=tmp_path)  # none of the scope modules exist here
    assert findings and all("stale scope entry" in f.message
                            for f in findings)


def test_logging_rule_fixture_pair():
    bad = 'print("hi")\n'
    findings, _, _ = run_on(LoggingHygieneRule(), {"stream/x.py": bad})
    assert len(findings) == 1 and "print()" in findings[0].message
    good = ('import logging\n'
            'log = logging.getLogger("fmda_tpu.x")\n')
    findings, _, _ = run_on(LoggingHygieneRule(), {"stream/x.py": good})
    assert not findings
    # allowlisted module: prints are its contract
    findings, _, _ = run_on(LoggingHygieneRule(), {"cli.py": bad})
    assert not findings


def test_span_clock_rule_fixture_pair():
    bad = ("import time\n"
           "t = time.time()\n")
    findings, _, _ = run_on(SpanClockRule(), {"obs/trace.py": bad})
    assert any("time.time()" in f.message for f in findings)
    good = ("import time\n"
            "t = time.perf_counter_ns()\n")
    findings, _, _ = run_on(SpanClockRule(), {"obs/trace.py": good})
    assert not findings


def test_chaos_rule_fixture_pair():
    bad = ("from fmda_tpu.chaos import default_chaos\n"
           "_CHAOS = default_chaos()\n"
           "def pump():\n"
           "    _CHAOS.check('router.pump')\n")
    findings, _, _ = run_on(ChaosGuardRule(), {"fleet/router.py": bad})
    assert any("outside an `if _CHAOS.enabled:`" in f.message
               for f in findings)
    good = bad.replace(
        "    _CHAOS.check('router.pump')",
        "    if _CHAOS.enabled:\n"
        "        _CHAOS.check('router.pump')")
    findings, _, _ = run_on(ChaosGuardRule(), {"fleet/router.py": good})
    assert not findings


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------


def test_baseline_round_trip_and_staleness(tmp_path):
    f1 = Finding("lock-discipline", "a.py", 3, "A.m: read of self.x")
    f2 = Finding("lock-discipline", "b.py", 9, "B.m: read of self.y")
    path = tmp_path / "baseline.json"
    save_baseline(
        [{**f1.as_dict(), "justification": "deliberate snapshot read"}],
        path)
    entries = load_baseline(path)
    new, old, stale = apply_baseline([f1, f2], entries)
    assert [f.key for f in old] == [f1.key]
    assert [f.key for f in new] == [f2.key]
    assert not stale
    # the grandfathered finding moved lines: still matched (key is
    # line-free); once fixed, the entry reports stale
    moved = Finding(f1.rule, f1.path, 77, f1.message)
    new, old, stale = apply_baseline([moved], entries)
    assert old and not new and not stale
    new, old, stale = apply_baseline([], entries)
    assert stale and stale[0]["path"] == "a.py"


def test_baseline_requires_justification(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({
        "version": 1,
        "findings": [{"rule": "r", "path": "p.py", "message": "m",
                      "justification": "  "}],
    }))
    with pytest.raises(ValueError, match="justification"):
        load_baseline(path)


def test_baseline_rejects_unknown_version(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"version": 99, "findings": []}))
    with pytest.raises(ValueError, match="version"):
        load_baseline(path)


# ---------------------------------------------------------------------------
# CLI contract + --json schema stability
# ---------------------------------------------------------------------------


def test_lint_json_schema(capsys):
    from fmda_tpu import cli

    rc = cli.main(["lint", "--json", "--no-drift"])
    doc = json.loads(capsys.readouterr().out)
    # schema is load-bearing for CI scripts: extend, don't rename
    assert set(doc) == {"ok", "n_modules", "new", "baselined",
                        "suppressed", "stale_baseline",
                        "forbidden_baseline", "reports"}
    assert doc["ok"] is True and rc == 0
    assert doc["n_modules"] > 50
    assert "bus_topics" in doc["reports"]


def test_lint_unknown_rule_is_usage_error(capsys):
    from fmda_tpu import cli

    rc = cli.main(["lint", "--rule", "no-such-rule", "--no-drift"])
    assert rc == 2
    assert "unknown rule" in capsys.readouterr().err


def test_lock_rule_sees_through_match_statements():
    # a lock acquired inside a `match` case must not read as unlocked
    # (and writes there must still mark the attribute guarded)
    src = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.n = 0\n"
           "    def bump(self, kind):\n"
           "        match kind:\n"
           "            case 'inc':\n"
           "                with self._lock:\n"
           "                    self.n += 1\n"
           "    def peek(self):\n"
           "        return self.n\n")
    findings, _, _ = run_on(LockDisciplineRule(), {"mod.py": src})
    assert len(findings) == 1
    assert "C.peek" in findings[0].message


def test_lint_stale_baseline_entry_fails_the_gate(capsys, tmp_path):
    # a paid-off debt left in the baseline exits 1 — the CLI and the
    # tier-1 test agree on `LintResult.ok`
    from fmda_tpu import cli

    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({
        "version": 1,
        "findings": [{"rule": "lock-discipline", "path": "gone.py",
                      "message": "paid off long ago",
                      "justification": "was deliberate once"}],
    }))
    rc = cli.main(["lint", "--no-drift", "--baseline", str(path)])
    captured = capsys.readouterr()
    assert rc == 1
    assert "stale baseline entry" in captured.err
    assert "1 stale baseline entry" in captured.out


def test_lint_drift_report_without_drift_rule_is_usage_error(
        capsys, tmp_path):
    from fmda_tpu import cli

    out = tmp_path / "drift.json"
    rc = cli.main(["lint", "--no-drift", "--drift-report", str(out)])
    assert rc == 2
    assert "--no-drift" in capsys.readouterr().err
    assert not out.exists()


def test_lint_missing_explicit_baseline_is_usage_error(capsys, tmp_path):
    # only the DEFAULT baseline may be absent; a typo'd --baseline must
    # not silently gate against an empty register
    from fmda_tpu import cli

    rc = cli.main(["lint", "--no-drift",
                   "--baseline", str(tmp_path / "nope.json")])
    assert rc == 2
    assert "baseline file not found" in capsys.readouterr().err


def test_lint_single_rule_filter(capsys):
    from fmda_tpu import cli

    rc = cli.main(["lint", "--rule", "lock-discipline", "--no-drift"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "0 new finding(s)" in out
    # rule filtering must not report other rules' baseline as stale
    assert "0 stale baseline entries" in out


# ---------------------------------------------------------------------------
# THE gate: the whole suite runs clean against the shipped baseline
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def repo_lint_result():
    """One full-suite run shared by the tier-1 gate tests — the drift
    resolver's jax imports make each run seconds, not milliseconds."""
    return run_lint(default_rules())


def test_repo_is_lint_clean_against_baseline(repo_lint_result):
    """Tier-1 equivalent of ``python -m fmda_tpu lint`` exiting 0: zero
    non-baselined findings across every rule (drift + compat-required
    included), no stale debt entries hiding in the baseline, and no
    entries smuggled under a zero-baseline rule."""
    result = repo_lint_result
    assert result.n_modules > 50
    assert not result.new, "new static-analysis findings:\n" + "\n".join(
        f.format() for f in result.new)
    assert not result.stale_baseline, (
        "baseline entries whose debt was paid — prune them:\n"
        + json.dumps(result.stale_baseline, indent=2))
    assert not result.forbidden_baseline, (
        "baseline entries for zero-baseline rules — fix the code:\n"
        + json.dumps(result.forbidden_baseline, indent=2))
    # the kernel surface carries ZERO drift against the installed jax,
    # under an EMPTY drift baseline (the 84-test failure set retired in
    # PR 9 stays retired: a fifth drifted symbol fails this test the
    # commit it appears, with nowhere to grandfather it)
    rep = result.reports["jax_api_drift"]
    assert rep["n_symbols"] == 0, (
        "jax API drift on the kernel surface:\n"
        + json.dumps(rep["symbols"], indent=2))
    drift_entries = [e for e in load_baseline()
                     if e["rule"] == "jax-api-drift"]
    assert drift_entries == []


def test_committed_drift_artifact_matches_live_scan(repo_lint_result):
    """``artifacts/jax_api_drift.json`` is the committed inventory other
    docs cite — it must stay bit-in-sync with what the scanner reports
    live, or the artifact silently rots (regenerate with
    ``python -m fmda_tpu lint --drift-report artifacts/jax_api_drift.json``).
    """
    artifact = PACKAGE_DIR.parent / "artifacts" / "jax_api_drift.json"
    assert artifact.is_file(), f"missing committed artifact: {artifact}"
    committed = json.loads(artifact.read_text())
    live = repo_lint_result.reports["jax_api_drift"]
    assert committed == live, (
        "committed drift artifact out of sync with a live scanner run — "
        "regenerate it:\n  python -m fmda_tpu lint --drift-report "
        "artifacts/jax_api_drift.json")


# ---------------------------------------------------------------------------
# metric-names (ISSUE 13 satellite)
# ---------------------------------------------------------------------------

METRICS_TP = """\
def wire(registry):
    registry.counter("fmda_double_prefixed_total")
    registry.gauge("bad-name")
    registry.counter("two_kinds")
    registry.gauge("two_kinds")
    registry.counter("split_series_total", topic="x")
    registry.counter("split_series_total", stream="x")
"""

METRICS_TN = """\
def wire(registry, metrics):
    registry.counter("requests_total")
    registry.counter("requests_total")  # same site shape: no conflict
    registry.gauge("queue_depth", process="w0")
    registry.gauge("queue_depth", process="w1")  # same key set
    registry.histogram("request_seconds")
    # RuntimeMetrics-style value setters (two positionals) are a
    # different vocabulary — not a registry registration
    metrics.gauge("active_sessions", 3)
    name = "dynamic"
    registry.counter(name)  # dynamic names are skipped

def collector():
    return {"counters": [
        {"name": "emitted_total", "labels": {}, "value": 1},
        {"name": "emitted_total", "labels": {}, "value": 2},
        {"name": f"{'x'}_total", "labels": {}, "value": 3},  # dynamic
    ]}
"""


def test_metric_names_flags_bad_registrations():
    from fmda_tpu.analysis import MetricNamesRule

    findings, _, _ = run_on(MetricNamesRule(), {"mod.py": METRICS_TP})
    msgs = [f.message for f in findings]
    assert any("fmda_double_prefixed_total" in m and "prefix" in m
               for m in msgs)
    assert any("bad-name" in m and "grammar" in m for m in msgs)
    assert any("two_kinds" in m and "instrument kinds" in m for m in msgs)
    assert any("split_series_total" in m and "label-key" in m
               for m in msgs)
    assert len(findings) == 4


def test_metric_names_clean_paths_and_report():
    from fmda_tpu.analysis import MetricNamesRule

    findings, _, ctx = run_on(MetricNamesRule(), {"mod.py": METRICS_TN})
    assert findings == []
    report = ctx.reports["metric_names"]
    assert "requests_total" in report["names"]
    assert "emitted_total" in report["names"]
    assert "active_sessions" not in report["names"]  # value setter


def test_metric_names_sample_vs_call_label_mismatch_flags():
    from fmda_tpu.analysis import MetricNamesRule

    src = (
        "def a(registry):\n"
        "    registry.counter('served_total', topic='x')\n"
        "def b():\n"
        "    return {'counters': [\n"
        "        {'name': 'served_total', 'labels': {'stream': 'y'},\n"
        "         'value': 1}]}\n"
    )
    findings, _, _ = run_on(MetricNamesRule(), {"mod.py": src})
    assert len(findings) == 1
    assert "served_total" in findings[0].message


# ---------------------------------------------------------------------------
# counted-loss: exception accounting + the conservation vocabulary (ISSUE 15)
# ---------------------------------------------------------------------------

SWALLOW_TP = """\
class Pump:
    def pump(self):
        try:
            self.bus.publish("t", {})
        except ConnectionError:
            pass
"""


def test_counted_loss_flags_silent_swallow():
    findings, _, _ = run_on(CountedLossRule(), {"fleet/x.py": SWALLOW_TP})
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "counted-loss"
    assert "Pump.pump" in f.message and "ConnectionError" in f.message


def test_counted_loss_out_of_scope_module_skipped():
    # the hot packages only: the same swallow in e.g. data/ is not this
    # rule's business
    findings, _, _ = run_on(CountedLossRule(), {"data/x.py": SWALLOW_TP})
    assert not findings


def test_counted_loss_clean_shapes():
    # the four sanctioned outs: re-raise, direct count, `+=` tally,
    # and the dict-tally assign
    src = (
        "class Pump:\n"
        "    def a(self):\n"
        "        try:\n"
        "            work()\n"
        "        except ValueError as e:\n"
        "            raise RuntimeError('no') from e\n"
        "    def b(self):\n"
        "        try:\n"
        "            work()\n"
        "        except ConnectionError:\n"
        "            self.metrics.count('bus_errors')\n"
        "    def c(self):\n"
        "        try:\n"
        "            work()\n"
        "        except OSError:\n"
        "            self.errors += 1\n"
        "    def d(self, skips, topic):\n"
        "        try:\n"
        "            work()\n"
        "        except OSError:\n"
        "            skips[topic] = skips.get(topic, 0) + 1\n"
    )
    findings, _, _ = run_on(CountedLossRule(), {"fleet/x.py": src})
    assert not findings


def test_counted_loss_one_level_callee_counts():
    # the interprocedural TN: the handler delegates its accounting to a
    # same-module callee whose body counts (fleet/worker.py's
    # _publish_control_counted is the real-repo instance)
    src = (
        "class W:\n"
        "    def _record(self):\n"
        "        self.metrics.count('control_errors')\n"
        "    def beat(self):\n"
        "        try:\n"
        "            self.bus.publish('t', {})\n"
        "        except ConnectionError:\n"
        "            self._record()\n"
    )
    findings, _, _ = run_on(CountedLossRule(), {"fleet/w.py": src})
    assert not findings
    # a callee that does NOT count leaves the handler unaccounted
    bad = src.replace("self.metrics.count('control_errors')", "pass")
    findings, _, _ = run_on(CountedLossRule(), {"fleet/w.py": bad})
    assert len(findings) == 1


def test_counted_loss_loss_free_hatch():
    hatched = SWALLOW_TP.replace(
        "        except ConnectionError:",
        "        # loss-free: teardown path, nothing in flight\n"
        "        except ConnectionError:")
    findings, _, _ = run_on(CountedLossRule(), {"fleet/x.py": hatched})
    assert not findings
    # the marker may sit anywhere in the contiguous comment block above
    wrapped = SWALLOW_TP.replace(
        "        except ConnectionError:",
        "        # loss-free: teardown path — nothing was in flight\n"
        "        # on this connection, so nothing can be lost\n"
        "        except ConnectionError:")
    findings, _, _ = run_on(CountedLossRule(), {"fleet/x.py": wrapped})
    assert not findings
    # reasonless = inert, same contract as # lock-free:
    bare = SWALLOW_TP.replace(
        "        except ConnectionError:",
        "        # loss-free:\n"
        "        except ConnectionError:")
    findings, _, _ = run_on(CountedLossRule(), {"fleet/x.py": bare})
    assert len(findings) == 1


def test_counted_loss_vocabulary_dead_term():
    # a gate summing a counter nobody increments is a silently weakened
    # identity — the cross-check reads the tuple the soak declares
    soak = 'LOSS_COUNTERS = ("results_missing", "ghost_losses")\n'
    router = (
        "class R:\n"
        "    def age(self):\n"
        "        self.metrics.count('results_missing')\n"
    )
    findings, _, _ = run_on(
        CountedLossRule(),
        {"chaos/soak.py": soak, "fleet/router.py": router})
    assert len(findings) == 1
    f = findings[0]
    assert f.path == "chaos/soak.py" and f.severity == "error"
    assert "ghost_losses" in f.message and "dead term" in f.message


def test_counted_loss_drop_site_outside_the_identity():
    soak = 'LOSS_COUNTERS = ("results_missing",)\n'
    router = (
        "class R:\n"
        "    def age(self):\n"
        "        self.metrics.count('results_missing')\n"
        "    def shed(self, n):\n"
        "        self.metrics.count('ticks_dropped', n)\n"
    )
    findings, _, _ = run_on(
        CountedLossRule(),
        {"chaos/soak.py": soak, "fleet/router.py": router})
    assert len(findings) == 1
    assert "ticks_dropped" in findings[0].message
    assert "never sums" in findings[0].message
    # the standard in-place hatch sanctions a deliberate non-gate series
    hatched = router.replace(
        "        self.metrics.count('ticks_dropped', n)",
        "        # lint: ignore[counted-loss] diagnostic-only series\n"
        "        self.metrics.count('ticks_dropped', n)")
    findings, suppressed, _ = run_on(
        CountedLossRule(),
        {"chaos/soak.py": soak, "fleet/router.py": hatched})
    assert not findings and suppressed == 1


# ---------------------------------------------------------------------------
# wire-protocol: op/kind cross-check + the v2 dialect (ISSUE 15)
# ---------------------------------------------------------------------------


def test_protocol_consumed_only_op_flags():
    # a dispatcher branch for an op no client ever sends: dead protocol
    # surface (or the producer's literal is typo'd)
    server = (
        "class S:\n"
        "    def dispatch(self, req):\n"
        "        op = req.get('op')\n"
        "        if op == 'publish':\n"
        "            return 1\n"
        "        if op == 'fetch_all':\n"
        "            return 2\n"
        "    def send(self):\n"
        "        self._request({'op': 'publish', 'topic': 't'})\n"
    )
    findings, _, _ = run_on(WireProtocolRule(), {"fleet/wire.py": server})
    assert len(findings) == 1
    assert "'fetch_all'" in findings[0].message
    assert "never produced" in findings[0].message


def test_protocol_produced_only_kind_flags_and_symmetric_clean():
    router = (
        "class R:\n"
        "    def a(self):\n"
        "        self._enqueue({'kind': 'tick', 'seq': 1})\n"
        "    def b(self):\n"
        "        self._enqueue({'kind': 'mystery'})\n"
    )
    worker = (
        "class W:\n"
        "    def apply(self, msg):\n"
        "        kind = msg.get('kind')\n"
        "        if kind == 'tick':\n"
        "            pass\n"
    )
    findings, _, _ = run_on(
        WireProtocolRule(),
        {"fleet/router.py": router, "fleet/worker.py": worker})
    assert len(findings) == 1
    assert "'mystery'" in findings[0].message
    assert "no consumer branch" in findings[0].message


def test_protocol_resolves_constants_and_param_flow():
    # the heartbeat shape: kinds produced by passing module constants
    # through a helper that stamps {"kind": kind} — the program index's
    # one-level parameter flow must resolve them, and the consumer side
    # compares against the imported constant names
    membership = (
        "HELLO = 'hello'\n"
        "GOODBYE = 'goodbye'\n"
        "class H:\n"
        "    def _publish(self, kind, stats):\n"
        "        self.bus.publish('t', {'kind': kind, 'stats': stats})\n"
        "    def hello(self):\n"
        "        self._publish(HELLO, None)\n"
        "    def goodbye(self):\n"
        "        self._publish(GOODBYE, None)\n"
    )
    router = (
        "class R:\n"
        "    def handle(self, msg):\n"
        "        kind = msg.get('kind')\n"
        "        if kind in (HELLO, GOODBYE):\n"
        "            return True\n"
    )
    findings, _, ctx = run_on(
        WireProtocolRule(),
        {"fleet/membership.py": membership, "fleet/router.py": router})
    assert not findings
    rep = ctx.reports["wire_protocol"]
    assert set(rep["kinds"]["produced"]) == {"hello", "goodbye"}
    assert set(rep["kinds"]["consumed"]) == {"hello", "goodbye"}


def test_protocol_local_constant_production():
    # router.stop_workers' shape: {"kind": kind} where kind is a local
    # `"drain_all" if graceful else "stop"`
    router = (
        "class R:\n"
        "    def stop_workers(self, graceful):\n"
        "        kind = 'drain_all' if graceful else 'stop'\n"
        "        self._enqueue({'kind': kind})\n"
    )
    worker = (
        "class W:\n"
        "    def apply(self, msg):\n"
        "        kind = msg.get('kind')\n"
        "        if kind in ('drain_all', 'stop'):\n"
        "            self.shutdown()\n"
    )
    findings, _, _ = run_on(
        WireProtocolRule(),
        {"fleet/router.py": router, "fleet/worker.py": worker})
    assert not findings


def test_protocol_v2_wire_default_must_stay_legacy():
    worker = (
        "class W:\n"
        "    def apply(self, msg):\n"
        "        return int(msg.get('wire', 2))\n"
    )
    findings, _, _ = run_on(WireProtocolRule(), {"fleet/worker.py": worker})
    assert len(findings) == 1
    assert "pre-v2" in findings[0].message
    ok = worker.replace("msg.get('wire', 2)", "msg.get('wire', 1)")
    findings, _, _ = run_on(WireProtocolRule(), {"fleet/worker.py": ok})
    assert not findings


def test_protocol_tick_blocks_need_a_lowering():
    bare = (
        "from fmda_tpu.stream import codec\n"
        "class R:\n"
        "    def send(self, msgs):\n"
        "        return codec.coalesce_ticks(msgs)\n"
    )
    findings, _, _ = run_on(WireProtocolRule(), {"fleet/router.py": bare})
    assert len(findings) == 1
    assert "legacy lowering" in findings[0].message
    lowered = bare.replace(
        "from fmda_tpu.stream import codec\n",
        "from fmda_tpu.stream import codec\n"
        "from fmda_tpu.fleet.state import to_legacy_msgs\n").replace(
        "        return codec.coalesce_ticks(msgs)\n",
        "        if self.legacy:\n"
        "            return to_legacy_msgs(msgs)\n"
        "        return codec.coalesce_ticks(msgs)\n")
    findings, _, _ = run_on(WireProtocolRule(), {"fleet/router.py": lowered})
    assert not findings


def test_protocol_pack_results_must_be_guarded():
    bare = (
        "class G:\n"
        "    def publish(self, results):\n"
        "        return pack_results(results, self.labels)\n"
    )
    findings, _, _ = run_on(WireProtocolRule(), {"runtime/gateway.py": bare})
    assert len(findings) == 1
    assert "per-tick result dialect" in findings[0].message
    guarded = bare.replace(
        "        return pack_results(results, self.labels)\n",
        "        if self.result_blocks:\n"
        "            return pack_results(results, self.labels)\n"
        "        return results\n")
    findings, _, _ = run_on(
        WireProtocolRule(), {"runtime/gateway.py": guarded})
    assert not findings


# ---------------------------------------------------------------------------
# thread-lifecycle (ISSUE 15)
# ---------------------------------------------------------------------------


def test_thread_rule_flags_unjoined_non_daemon():
    src = (
        "import threading\n"
        "class S:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self.run)\n"
        "        self._t.start()\n"
    )
    findings, _, _ = run_on(ThreadLifecycleRule(), {"obs/x.py": src})
    assert len(findings) == 1
    assert "self._t" in findings[0].message
    assert "join" in findings[0].message


def test_thread_rule_daemon_and_joined_on_close_are_clean():
    daemon = (
        "import threading\n"
        "class S:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self.run, daemon=True)\n"
        "        self._t.start()\n"
    )
    findings, _, _ = run_on(ThreadLifecycleRule(), {"obs/x.py": daemon})
    assert not findings
    # the joined-on-close TN: a non-daemon thread whose owner settles it
    joined = (
        "import threading\n"
        "class S:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self.run)\n"
        "        self._t.start()\n"
        "    def stop(self):\n"
        "        self._t.join(timeout=5.0)\n"
    )
    findings, _, _ = run_on(ThreadLifecycleRule(), {"obs/x.py": joined})
    assert not findings


def test_thread_rule_timer_cancel_and_local_join():
    timer = (
        "import threading\n"
        "class S:\n"
        "    def arm(self):\n"
        "        self._timer = threading.Timer(5.0, self.fire)\n"
        "        self._timer.start()\n"
        "    def close(self):\n"
        "        self._timer.cancel()\n"
    )
    findings, _, _ = run_on(ThreadLifecycleRule(), {"obs/x.py": timer})
    assert not findings
    local = (
        "from threading import Thread\n"
        "def run_all(jobs):\n"
        "    t = Thread(target=jobs.pop)\n"
        "    t.start()\n"
        "    t.join()\n"
    )
    findings, _, _ = run_on(ThreadLifecycleRule(), {"obs/y.py": local})
    assert not findings


def test_thread_rule_fire_and_forget_flags():
    src = (
        "import threading\n"
        "def kick(fn):\n"
        "    threading.Thread(target=fn).start()\n"
    )
    findings, _, _ = run_on(ThreadLifecycleRule(), {"fleet/x.py": src})
    assert len(findings) == 1
    assert "fire-and-forget" in findings[0].message
    # alias-aware both ways, like the other import-tracking rules
    aliased = (
        "from threading import Thread as T\n"
        "def kick(fn):\n"
        "    T(target=fn).start()\n"
    )
    findings, _, _ = run_on(ThreadLifecycleRule(), {"fleet/x.py": aliased})
    assert len(findings) == 1


# ---------------------------------------------------------------------------
# SARIF export (ISSUE 15 satellite) — schema is load-bearing for CI
# ---------------------------------------------------------------------------


def test_sarif_document_schema():
    result = LintResult(
        new=[Finding("counted-loss", "fleet/x.py", 3, "swallowed", "warning")],
        baselined=[Finding("lock-discipline", "obs/y.py", 7, "old debt",
                           "warning")],
    )
    rules = default_rules(drift=False)
    doc = to_sarif(result, rules)
    assert set(doc) == {"$schema", "version", "runs"}
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "fmda-tpu-lint"
    ids = {r["id"] for r in driver["rules"]}
    assert {"counted-loss", "wire-protocol", "thread-lifecycle"} <= ids
    assert all(set(r) == {"id", "shortDescription", "defaultConfiguration"}
               for r in driver["rules"])
    new, old = run["results"]
    assert set(new) == {"ruleId", "level", "message", "locations"}
    assert new["ruleId"] == "counted-loss" and new["level"] == "warning"
    loc = new["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"] == {"uri": "fmda_tpu/fleet/x.py",
                                       "uriBaseId": "SRCROOT"}
    assert loc["region"] == {"startLine": 3}
    # grandfathered findings export as externally suppressed results —
    # visible to the scanner, non-blocking
    assert old["suppressions"][0]["kind"] == "external"


def test_lint_sarif_cli_writes_document(tmp_path):
    from fmda_tpu import cli

    out = tmp_path / "lint.sarif"
    rc = cli.main(["lint", "--no-drift", "--sarif", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["version"] == "2.1.0"
    assert doc["runs"][0]["results"] == []  # the repo is clean
    rule_ids = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert "wire-protocol" in rule_ids and "jax-api-drift" not in rule_ids


# ---------------------------------------------------------------------------
# the tier-1 gate, extended to the never-abort rules (ISSUE 15)
# ---------------------------------------------------------------------------

NEVER_ABORT_RULES = ("counted-loss", "wire-protocol", "thread-lifecycle")


def test_never_abort_rules_hold_zero_findings(repo_lint_result):
    """Stronger than "zero NEW": the three ISSUE-15 rules hold the repo
    at zero findings outright — no baseline entries, nothing
    grandfathered.  Deliberate exceptions are annotated in place, where
    the next reader sees the reason."""
    result = repo_lint_result
    hits = [f for f in result.new + result.baselined
            if f.rule in NEVER_ABORT_RULES]
    assert hits == [], "\n".join(f.format() for f in hits)
    assert [e for e in load_baseline()
            if e["rule"] in NEVER_ABORT_RULES] == []


def test_conservation_vocabulary_cross_check_green(repo_lint_result):
    """The gates' loss sets resolve against counters the code really
    increments, and the wire harvest sees the live protocol — pins the
    cross-checks to the actual repo, not just fixtures."""
    rep = repo_lint_result.reports["counted_loss"]
    declared = {n for names in rep["vocabulary"].values() for n in names}
    assert {"results_missing", "migration_buffer_shed",
            "inflight_dropped_on_close"} <= declared
    assert "stale_results_dropped" in declared  # the gap this PR closed
    assert declared <= set(rep["registered_counters"])
    # the pipeline gate's vocabulary is declared, not an inline dict
    assert set(rep["pipeline_loss_fields"]) == {
        "dropped_unjoinable", "pending_joins",
        "journal_pending", "journal_shed"}
    wire = repo_lint_result.reports["wire_protocol"]
    assert {"tick", "tick_block", "open", "drain_session",
            "session_state", "result_block"} <= set(
        wire["kinds"]["produced"])
    # the interprocedural resolution: hello/heartbeat/goodbye are
    # produced only via Heartbeater._publish's kind parameter
    assert {"hello", "heartbeat", "goodbye"} <= set(
        wire["kinds"]["produced"])
    assert {"publish", "read", "batch", "hello"} <= set(
        wire["ops"]["produced"])


def test_counted_loss_marker_does_not_bleed_to_next_handler():
    # a previous handler's same-line hatch (a trailing comment on a
    # CODE line) must not exempt the handler below it
    src = (
        "class P:\n"
        "    def go(self):\n"
        "        try:\n"
        "            work()\n"
        "        except ValueError:\n"
        "            pass  # loss-free: benign probe\n"
        "        except ConnectionError:\n"
        "            pass\n"
    )
    findings, _, _ = run_on(CountedLossRule(), {"fleet/x.py": src})
    assert len(findings) == 2  # the marker sanctions NEITHER handler:
    # it trails a code line inside handler A's body (put it on the
    # `except` line or above), and it must not bleed into handler B
    # and a stale marker trailing the last try-body statement doesn't
    # sanction the handler either
    trailing = (
        "class P:\n"
        "    def go(self):\n"
        "        try:\n"
        "            work()  # loss-free: stale note on a code line\n"
        "        except ConnectionError:\n"
        "            pass\n"
    )
    findings, _, _ = run_on(CountedLossRule(), {"fleet/x.py": trailing})
    assert len(findings) == 1


def test_protocol_param_flow_resolves_keyword_calls():
    # a keyword-argument call into a kind-stamping helper must still
    # register the production (a refactor to kwargs is not a protocol
    # change)
    membership = (
        "HELLO = 'hello'\n"
        "class H:\n"
        "    def _publish(self, kind, stats):\n"
        "        self.bus.publish('t', {'kind': kind, 'stats': stats})\n"
        "    def hello(self):\n"
        "        self._publish(kind=HELLO, stats=None)\n"
    )
    router = (
        "class R:\n"
        "    def handle(self, msg):\n"
        "        kind = msg.get('kind')\n"
        "        if kind == 'hello':\n"
        "            return True\n"
    )
    findings, _, _ = run_on(
        WireProtocolRule(),
        {"fleet/membership.py": membership, "fleet/router.py": router})
    assert not findings


def test_thread_rule_annotated_assignment_tracked():
    # an AnnAssign-bound thread is owned like a plain assignment: the
    # joined-on-close shape stays clean, the unjoined one is flagged as
    # bound (never as fire-and-forget)
    joined = (
        "import threading\n"
        "class S:\n"
        "    def start(self):\n"
        "        self._t: threading.Thread = "
        "threading.Thread(target=self.run)\n"
        "        self._t.start()\n"
        "    def stop(self):\n"
        "        self._t.join(timeout=5.0)\n"
    )
    findings, _, _ = run_on(ThreadLifecycleRule(), {"obs/x.py": joined})
    assert not findings
    unjoined = joined.replace(
        "    def stop(self):\n        self._t.join(timeout=5.0)\n", "")
    findings, _, _ = run_on(ThreadLifecycleRule(), {"obs/x.py": unjoined})
    assert len(findings) == 1
    assert "self._t" in findings[0].message
    assert "fire-and-forget" not in findings[0].message
