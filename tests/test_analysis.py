"""symlint (``repro.analysis``): rule fixtures, baseline/suppression
mechanics, the SL002-SL005 mutation batteries, the CFG dataflow paths, the
deep tier (SL006-SL008) with seeded defects, and the repo-wide smoke gate.

Every fixture project is built in ``tmp_path`` and analyzed through the real
engine (``load_project`` + ``analyze``), so the tests exercise the same
suppression/baseline partitioning the CLI uses.  The mutation batteries copy
*actual* repo files, seed one defect (one-sided struct edit, dropped
donation rebind, traced branch, un-annotated sync, gutted pretrace ladder,
f64 upcast), and assert the owning rule catches it -- that is the property
each rule exists for.
"""
import json
import subprocess
from pathlib import Path

import pytest

from repro.analysis.cli import find_root, main
from repro.analysis.engine import Baseline, analyze, load_project

REPO_ROOT = find_root(Path(__file__).resolve().parent)


def run(tmp_path, sources, rules, baseline=None, deep=False):
    """Write ``{relpath: source}`` under tmp_path and analyze it."""
    for rel, text in sources.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    project = load_project(tmp_path, [tmp_path])
    if deep:
        from repro.analysis import deep as deep_mod
        deep_mod.prepare(project)
    return analyze(project, rules, baseline, include_deep=deep)


def rules_of(result):
    return [f.rule for f in result.findings]


# --------------------------------------------------------------- SL001 compat


SL001_POS = """\
import jax
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.shard_map import shard_map

def kernel(block):
    return pltpu.MemorySpace.ANY

def grid(params):
    return params(dimension_semantics=("parallel",))

def mesh():
    return jax.make_mesh((1,), ("data",))
"""

SL001_NEG = """\
from repro.utils.jax_compat import MemorySpace, VMEM, tpu_compiler_params

def kernel(block):
    return MemorySpace.ANY, VMEM((8,), float)
"""


class TestSL001:
    def test_positive(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL001_POS}, ["SL001"])
        msgs = [f.message for f in result.findings]
        assert len(result.findings) == 4
        assert any("jax.experimental.shard_map" in m for m in msgs)
        assert any("pltpu.MemorySpace" in m for m in msgs)
        assert any("dimension_semantics" in m for m in msgs)
        assert any("jax.make_mesh" in m for m in msgs)

    def test_negative(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL001_NEG}, ["SL001"])
        assert result.findings == []

    def test_suppressed(self, tmp_path):
        src = SL001_POS.replace(
            "return pltpu.MemorySpace.ANY",
            "return pltpu.MemorySpace.ANY  # symlint: disable=SL001")
        result = run(tmp_path, {"mod.py": src}, ["SL001"])
        assert len(result.findings) == 3
        assert len(result.suppressed) == 1

    def test_baselined(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL001_POS}, ["SL001"])
        bpath = tmp_path / "baseline.json"
        Baseline.write(bpath, result.findings, {})
        baseline = Baseline(bpath)
        again = run(tmp_path, {"mod.py": SL001_POS}, ["SL001"], baseline)
        assert again.findings == []
        assert len(again.baselined) == 4
        assert again.exit_code == 0

    def test_compat_module_itself_exempt(self, tmp_path):
        result = run(
            tmp_path, {"utils/jax_compat.py": SL001_POS}, ["SL001"])
        assert result.findings == []

    def test_docstring_table_drives_banned_list(self, tmp_path):
        # a fixture jax_compat whose table bans a made-up name
        compat = (
            '"""Shims.\n\n'
            "====  ====\n"
            "a     b\n"
            "====  ====\n"
            "x     ``pltpu.MadeUpName``\n"
            "====  ====\n"
            '"""\n'
        )
        user = (
            "from jax.experimental.pallas import tpu as pltpu\n"
            "def f():\n"
            "    return pltpu.MadeUpName\n"
        )
        result = run(tmp_path, {"utils/jax_compat.py": compat,
                                "mod.py": user}, ["SL001"])
        assert [f.rule for f in result.findings] == ["SL001"]
        assert "MadeUpName" in result.findings[0].message


# -------------------------------------------------------------- SL002 retrace


SL002_BRANCH = """\
import jax

@jax.jit
def f(x, y):
    if x > 0:
        return y
    return -y
"""

SL002_STATIC_OK = """\
import functools
import jax

@functools.partial(jax.jit, static_argnames=("first",))
def f(x, *, first):
    if first:
        return x * 2
    return x
"""

SL002_CONCRETIZE = """\
import jax

@jax.jit
def f(x):
    return float(x) + 1.0
"""

SL002_CLOSURE = """\
import jax

def outer(scale):
    @jax.jit
    def inner(x):
        return x * scale
    return inner
"""

SL002_LOOP_STATIC = """\
import functools
import jax

@functools.partial(jax.jit, static_argnames=("k",))
def f(x, *, k):
    return x * k

def driver(x):
    out = []
    for i in range(8):
        out.append(f(x, k=i))
    return out
"""


class TestSL002:
    def test_branch_on_traced(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL002_BRANCH}, ["SL002"])
        assert rules_of(result) == ["SL002"]
        assert "`if` statement" in result.findings[0].message

    def test_static_branch_ok(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL002_STATIC_OK}, ["SL002"])
        assert result.findings == []

    def test_none_check_ok(self, tmp_path):
        src = SL002_BRANCH.replace("if x > 0:", "if y is None:")
        result = run(tmp_path, {"mod.py": src}, ["SL002"])
        assert result.findings == []

    def test_concretize_traced(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL002_CONCRETIZE}, ["SL002"])
        assert rules_of(result) == ["SL002"]
        assert "float()" in result.findings[0].message

    def test_closure_capture(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL002_CLOSURE}, ["SL002"])
        assert rules_of(result) == ["SL002"]
        assert "`scale`" in result.findings[0].message

    def test_loop_varying_static(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL002_LOOP_STATIC}, ["SL002"])
        assert rules_of(result) == ["SL002"]
        assert "loop-varying" in result.findings[0].message

    def test_suppressed(self, tmp_path):
        src = SL002_BRANCH.replace(
            "if x > 0:", "if x > 0:  # symlint: disable=SL002")
        result = run(tmp_path, {"mod.py": src}, ["SL002"])
        assert result.findings == []
        assert len(result.suppressed) == 1


# ------------------------------------------------------------- SL003 donation


SL003_REUSE = """\
import jax

@jax.jit
def step(state, x):
    return state + x

step = jax.jit(step, donate_argnums=(0,))

def driver(state, x):
    out = step(state, x)
    return state + out
"""

SL003_REBOUND = """\
import jax

def _step(state, x):
    return state + x

step = jax.jit(_step, donate_argnums=(0,))

def driver(state, xs):
    for x in xs:
        state = step(state, x)
    return state
"""

SL003_LOOP_NO_REBIND = """\
import jax

def _step(state, x):
    return state + x

step = jax.jit(_step, donate_argnums=(0,))

def driver(state, xs):
    out = []
    for x in xs:
        out.append(step(state, x))
    return out
"""


class TestSL003:
    def test_read_after_donate(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL003_REUSE}, ["SL003"])
        assert "SL003" in rules_of(result)
        assert "`state`" in result.findings[0].message

    def test_rebound_ok(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL003_REBOUND}, ["SL003"])
        assert result.findings == []

    def test_loop_without_rebind(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL003_LOOP_NO_REBIND}, ["SL003"])
        assert "SL003" in rules_of(result)
        assert "loop" in result.findings[0].message

    def test_suppressed(self, tmp_path):
        src = SL003_REUSE.replace(
            "return state + out",
            "return state + out  # symlint: disable=SL003")
        result = run(tmp_path, {"mod.py": src}, ["SL003"])
        assert result.findings == []
        assert len(result.suppressed) == 1


# ------------------------------------------------------------- SL004 hostsync


SL004_SYNC = """\
import numpy as np
import jax.numpy as jnp

def hot(x):  # symlint: hot-path
    y = jnp.cumsum(x)
    return np.asarray(y)
"""

SL004_ANNOTATED = """\
import numpy as np
import jax
import jax.numpy as jnp

def hot(x):  # symlint: hot-path
    y = jnp.cumsum(x)
    return jax.device_get(y)  # sync: ok
"""

SL004_BRANCH = """\
import jax.numpy as jnp

def hot(x):  # symlint: hot-path
    y = jnp.any(x > 0)
    if y:
        return 1
    return 0
"""


class TestSL004:
    def test_sync_in_hot_path(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL004_SYNC}, ["SL004"])
        assert rules_of(result) == ["SL004"]
        assert "np.asarray()" in result.findings[0].message

    def test_annotated_sync_ok(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL004_ANNOTATED}, ["SL004"])
        assert result.findings == []

    def test_branch_on_device_value(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL004_BRANCH}, ["SL004"])
        assert rules_of(result) == ["SL004"]
        assert "blocks on the device" in result.findings[0].message

    def test_unmarked_function_ignored(self, tmp_path):
        src = SL004_SYNC.replace("def hot(x):  # symlint: hot-path",
                                 "def cold(x):")
        result = run(tmp_path, {"mod.py": src}, ["SL004"])
        assert result.findings == []

    def test_suppressed(self, tmp_path):
        src = SL004_SYNC.replace(
            "return np.asarray(y)",
            "return np.asarray(y)  # symlint: disable=SL004")
        result = run(tmp_path, {"mod.py": src}, ["SL004"])
        assert result.findings == []
        assert len(result.suppressed) == 1


# ----------------------------------------------------------------- SL005 wire


CODEC_FILES = ("src/repro/launch/transport.py", "src/repro/core/receiver.py")


def codec_sources():
    return {rel: (REPO_ROOT / rel).read_text() for rel in CODEC_FILES}


class TestSL005:
    def test_real_codecs_consistent(self, tmp_path):
        result = run(tmp_path, codec_sources(), ["SL005"])
        assert result.findings == []

    @pytest.mark.parametrize("before,after", [
        ('"!IIB"', '"!IBB"'),     # encode/decode_closed header
        ('"!fII"', '"!fIH"'),     # pieces DATA header
        ('("endpoint", ">f4")', '("endpoint", ">f8")'),  # piece record
    ])
    def test_mutation_caught(self, tmp_path, before, after):
        sources = codec_sources()
        mutated = False
        for rel in list(sources):
            if before in sources[rel]:
                # flip the *first* occurrence: a one-sided edit
                sources[rel] = sources[rel].replace(before, after, 1)
                mutated = True
                break
        assert mutated, f"pattern {before!r} not found in codec files"
        result = run(tmp_path, sources, ["SL005"])
        assert any(f.rule == "SL005" for f in result.findings), (
            f"one-sided {before} -> {after} edit not caught")

    def test_unpaired_codec_flagged(self, tmp_path):
        src = (
            "import struct\n"
            "def encode_open(sid, mode, seed):\n"
            "    return struct.pack('!BI', mode, seed)\n"
        )
        result = run(tmp_path, {"mod.py": src}, ["SL005"])
        assert any("decode_open" in f.message for f in result.findings)

    def test_offset_mismatch(self, tmp_path):
        src = (
            "import struct\n"
            "def encode_close(t, flag):\n"
            "    return struct.pack('!IB', t, flag) + struct.pack('!f', 0.5)\n"
            "def decode_close(buf):\n"
            "    t, flag = struct.unpack_from('!IB', buf)\n"
            "    tail = struct.unpack_from('!f', buf, 6)[0]\n"
            "    return t, flag, tail\n"
        )
        result = run(tmp_path, {"mod.py": src}, ["SL005"])
        assert any("offset 6" in f.message for f in result.findings)

    def test_constant_contract(self, tmp_path):
        src = (
            "import numpy as np\n"
            "DELTA_SYMBOL_BYTES = 6.0\n"
            '_DELTA_REC = np.dtype([("label", "u1"), ("endpoint", ">f4")])\n'
        )
        result = run(tmp_path, {"mod.py": src}, ["SL005"])
        assert any("DELTA_SYMBOL_BYTES" in f.message
                   for f in result.findings)


# --------------------------------------- SL002/SL003/SL004 mutation batteries
#
# Mirror TestSL005.test_mutation_caught: copy the *actual* repo file, seed
# one defect, and assert the owning rule catches it.  Each battery first
# asserts the clean copy passes, so a firing can only come from the seed.


STREAM_SRC = "src/repro/launch/stream.py"
SYMED_SRC = "src/repro/core/symed.py"


def repo_source(rel):
    return {rel: (REPO_ROOT / rel).read_text()}


class TestMutationBatteries:
    def test_sl002_traced_branch_caught(self, tmp_path):
        sources = repo_source(SYMED_SRC)
        assert run(tmp_path, sources, ["SL002"]).findings == []
        needle = "    chunk = jnp.asarray(chunk, jnp.float32)"
        assert needle in sources[SYMED_SRC]
        sources[SYMED_SRC] = sources[SYMED_SRC].replace(
            needle,
            needle + "\n    if chunk[0] > 0:\n        chunk = -chunk", 1)
        result = run(tmp_path, sources, ["SL002"])
        assert any(f.rule == "SL002" and "`if` statement" in f.message
                   for f in result.findings), rules_of(result)

    def test_sl003_dropped_rebind_caught(self, tmp_path):
        sources = repo_source(STREAM_SRC)
        assert run(tmp_path, sources, ["SL003"]).findings == []
        needle = "self._table, info = _table_step("
        assert needle in sources[STREAM_SRC]
        # dropped rebind: the donated resident table is no longer reassigned
        # from the step's result, so the next round donates a dead buffer
        sources[STREAM_SRC] = sources[STREAM_SRC].replace(
            needle, "_stale, info = _table_step(", 1)
        result = run(tmp_path, sources, ["SL003"])
        assert any(f.rule == "SL003" and "self._table" in f.message
                   for f in result.findings), rules_of(result)

    def test_sl004_unannotated_sync_caught(self, tmp_path):
        sources = repo_source(STREAM_SRC)
        assert run(tmp_path, sources, ["SL004"]).findings == []
        needle = '                self.totals["steps"] += 1'
        assert needle in sources[STREAM_SRC]
        # seed a per-round host sync on the step's device output inside the
        # hot-path ingest loop, without the reviewed `# sync: ok` marker
        sources[STREAM_SRC] = sources[STREAM_SRC].replace(
            needle,
            needle + '\n                _t0 = float(info["t_seen"][0])', 1)
        result = run(tmp_path, sources, ["SL004"])
        assert any(f.rule == "SL004" and "float()" in f.message
                   for f in result.findings), rules_of(result)


# ----------------------------------------------- CFG dataflow paths (fixpoint)


CFG_LOOP_CARRY = """\
import jax.numpy as jnp

def hot(xs, n):  # symlint: hot-path
    prev = None
    for i in range(n):
        if i > 0:
            out = float(prev)
        prev = jnp.sum(xs[i])
    return prev
"""

CFG_BRANCH_CLEANSE_ONE = """\
import jax.numpy as jnp

def hot(x, cond):  # symlint: hot-path
    v = jnp.sum(x)
    if cond:
        v = 0.0
    return float(v)
"""

CFG_BRANCH_CLEANSE_BOTH = """\
import jax.numpy as jnp

def hot(x, cond):  # symlint: hot-path
    v = jnp.sum(x)
    if cond:
        v = 0.0
    else:
        v = 1.0
    return float(v)
"""

CFG_TRY_EDGE = """\
import jax.numpy as jnp

def hot(x):  # symlint: hot-path
    v = 0.0
    try:
        v = jnp.sum(x)
        v = host_value()
    except ValueError:
        return float(v)
    return v
"""


class TestCFGDataflow:
    """Flows only a fixpoint over a real CFG can see (the single-pass
    walker this engine replaced read statements once, in source order)."""

    def test_loop_carried_taint(self, tmp_path):
        # `prev` is tainted at the *bottom* of the loop body; the read at
        # the top only sees it through the loop's back edge
        result = run(tmp_path, {"mod.py": CFG_LOOP_CARRY}, ["SL004"])
        assert any("float()" in f.message for f in result.findings), \
            rules_of(result)

    def test_cleanse_in_one_branch_still_tainted(self, tmp_path):
        result = run(tmp_path, {"mod.py": CFG_BRANCH_CLEANSE_ONE}, ["SL004"])
        assert any("float()" in f.message for f in result.findings), \
            rules_of(result)

    def test_cleanse_in_both_branches_clean(self, tmp_path):
        result = run(tmp_path, {"mod.py": CFG_BRANCH_CLEANSE_BOTH},
                     ["SL004"])
        assert result.findings == []

    def test_taint_reaches_handler_via_exception_edge(self, tmp_path):
        # the handler can run after `v = jnp.sum(x)` but before the
        # cleansing host_value() rebind lands
        result = run(tmp_path, {"mod.py": CFG_TRY_EDGE}, ["SL004"])
        assert any("float()" in f.message for f in result.findings), \
            rules_of(result)


# ------------------------------------------------------- engine + repo gates


class TestEngine:
    def test_stale_baseline_entry_fails(self, tmp_path):
        result = run(tmp_path, {"mod.py": SL001_POS}, ["SL001"])
        bpath = tmp_path / "baseline.json"
        Baseline.write(bpath, result.findings, {})
        clean = run(tmp_path, {"clean.py": SL001_NEG}, ["SL001"],
                    Baseline(bpath))
        # the fixture with the violations is still in the sweep, so entries
        # are live; now analyze a sweep where they no longer match
        project = load_project(tmp_path / "sub", [])
        from repro.analysis.engine import analyze as analyze_fn
        result2 = analyze_fn(project, ["SL001"], Baseline(bpath))
        assert result2.stale_baseline
        assert result2.exit_code == 1
        assert clean.exit_code == 0  # live entries are not stale

    def test_parse_error_reported_not_raised(self, tmp_path):
        result = run(tmp_path, {"bad.py": "def broken(:\n"}, ["SL001"])
        assert result.parse_errors
        assert result.exit_code == 1

    def test_bare_disable_suppresses_all_rules(self, tmp_path):
        src = SL002_BRANCH.replace(
            "if x > 0:", "if x > 0:  # symlint: disable")
        result = run(tmp_path, {"mod.py": src}, ["SL002"])
        assert result.findings == []
        assert len(result.suppressed) == 1

    def test_fingerprint_survives_line_moves(self, tmp_path):
        r1 = run(tmp_path, {"mod.py": SL002_BRANCH}, ["SL002"])
        shifted = "# a new leading comment\n\n" + SL002_BRANCH
        r2 = run(tmp_path, {"mod.py": shifted}, ["SL002"])
        assert (r1.findings[0].fingerprint
                == r2.findings[0].fingerprint)
        assert r1.findings[0].line != r2.findings[0].line


class TestCompatTablePin:
    def test_fallback_tokens_match_docstring_table(self):
        """The frozen fallback banned-name table must stay in lock-step with
        the table parsed live from jax_compat.py's docstring -- the fallback
        exists only for sweeps that exclude the compat module, never to
        diverge.  The live table also documents the shim-side replacement
        names (harmless in the pltpu-attr bucket), so the pin compares the
        *effective* banned sets: kwargs and dotted paths must be identical,
        every ``pltpu.``-prefixed ban identical, and every fallback token
        must still exist in the docstring."""
        from repro.analysis.rules.compat import (
            FALLBACK_TOKENS, _classify, _docstring_tokens)
        project = load_project(REPO_ROOT, [REPO_ROOT / "src"])
        live = _docstring_tokens(project)
        assert live is not FALLBACK_TOKENS, \
            "docstring table not found -- pin test is comparing the " \
            "fallback with itself"
        missing = set(FALLBACK_TOKENS) - set(live)
        assert not missing, f"fallback bans names the docstring dropped: " \
            f"{sorted(missing)}"
        live_kwargs, _, live_paths = _classify(live)
        fb_kwargs, _, fb_paths = _classify(FALLBACK_TOKENS)
        assert live_kwargs == fb_kwargs
        assert live_paths == fb_paths
        live_pltpu = {t for t in live if t.startswith("pltpu.")}
        fb_pltpu = {t for t in FALLBACK_TOKENS if t.startswith("pltpu.")}
        assert live_pltpu == fb_pltpu


# ------------------------------------------------- deep tier (SL006 - SL008)


ENTRY_GOOD = """\
import functools
import jax

@functools.partial(jax.jit, donate_argnums=(0,))  # symlint: entry(drive=stream, budget=2, shapes=table-step, pair=chunk/table)
def step(state, x):
    return state + x
"""


class TestEntryRegistry:
    """The annotation parser is pure AST -- no jax import involved."""

    def _registry(self, tmp_path, sources):
        for rel, text in sources.items():
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(text)
        from repro.analysis.deep import entry_registry
        return entry_registry(load_project(tmp_path, [tmp_path]))

    def test_parse_all_keys(self, tmp_path):
        entries, errors = self._registry(tmp_path, {"mod.py": ENTRY_GOOD})
        assert errors == []
        (e,) = entries
        assert (e.qualname, e.drive, e.budget, e.shapes) == (
            "step", "stream", 2, "table-step")
        assert (e.pair_label, e.pair_role) == ("chunk", "table")

    def test_inline_shapes_survive_comma_split(self, tmp_path):
        src = ENTRY_GOOD.replace(
            "entry(drive=stream, budget=2, shapes=table-step, "
            "pair=chunk/table)",
            "entry(budget=1, shapes=f32[4,8] i32[4], drive=stream)")
        entries, errors = self._registry(tmp_path, {"mod.py": src})
        assert errors == []
        assert entries[0].shapes == "f32[4,8] i32[4]"
        assert entries[0].budget == 1

    @pytest.mark.parametrize("mutant,expect", [
        ("drive=stream, budget=two", "not an int"),
        ("drive=stream, colour=red", "unknown"),
        ("pair=chunk", "slot or"),
        ("budget=0", "at least"),
    ])
    def test_malformed_annotation_is_error(self, tmp_path, mutant, expect):
        src = ENTRY_GOOD.replace(
            "entry(drive=stream, budget=2, shapes=table-step, "
            "pair=chunk/table)", f"entry({mutant})")
        entries, errors = self._registry(tmp_path, {"mod.py": src})
        assert entries == []
        assert len(errors) == 1 and expect in errors[0][2]

    def test_nested_def_is_error(self, tmp_path):
        src = (
            "def outer():\n"
            "    def inner(x):  # symlint: entry(drive=stream)\n"
            "        return x\n"
            "    return inner\n"
        )
        entries, errors = self._registry(tmp_path, {"mod.py": src})
        assert entries == []
        assert len(errors) == 1 and "module-level" in errors[0][2]

    def test_dangling_annotation_is_error(self, tmp_path):
        src = "x = 1  # symlint: entry(drive=stream)\n"
        entries, errors = self._registry(tmp_path, {"mod.py": src})
        assert entries == []
        assert len(errors) == 1 and "not attached" in errors[0][2]

    def test_repo_entries_present(self):
        from repro.analysis.deep import entry_registry
        paths = [REPO_ROOT / d for d in ("src", "examples", "benchmarks")
                 if (REPO_ROOT / d).is_dir()]
        entries, errors = entry_registry(load_project(REPO_ROOT, paths))
        assert errors == []
        names = {e.qualname for e in entries}
        assert {"_table_step", "_table_step_pieces", "_encode_chunk",
                "_receive_chunk", "_receive_finish", "digitize_span",
                "digitize_span_table", "digitize_pieces",
                "_mapped_runner"} <= names
        pairs = {(e.pair_label, e.pair_role) for e in entries
                 if e.pair_label}
        assert {("chunk", "slot"), ("chunk", "table"), ("pieces", "slot"),
                ("pieces", "table"), ("span", "slot"),
                ("span", "table")} <= pairs


class TestDeepTier:
    """Seeded-defect batteries: each deep rule must fire on a mutated copy
    of the real file it guards (and stay quiet without the seed -- HEAD
    cleanliness is asserted by CI's `symlint --deep` run, not re-paid here
    per test)."""

    def test_deep_rules_silent_without_prepare(self, tmp_path):
        result = run(tmp_path, {"mod.py": ENTRY_GOOD},
                     ["SL006", "SL007", "SL008"])
        assert result.findings == []

    def test_deep_rules_excluded_from_default_tier(self):
        from repro.analysis.engine import RULES
        import repro.analysis.rules  # noqa: F401
        assert {RULES[r].tier for r in ("SL006", "SL007", "SL008")} == {
            "deep"}
        assert {RULES[r].tier
                for r in ("SL001", "SL002", "SL003", "SL004", "SL005")} == {
            "ast"}

    def test_sl006_gutted_pretrace_trips_budget(self, tmp_path):
        text = (REPO_ROOT / STREAM_SRC).read_text()
        needle = ("ladder = self._ladder if self.autoscale "
                  "else [self.capacity]")
        assert needle in text
        # the warm-up no longer covers any rung: the first serving-loop
        # ingest of the measured window must now trace
        result = run(tmp_path,
                     {"stream_mut.py": text.replace(needle, "ladder = []")},
                     ["SL006"], deep=True)
        assert any(f.rule == "SL006" and "over its declared budget"
                   in f.message for f in result.findings), \
            [f.message for f in result.findings]

    def test_sl007_f64_upcast_trips_dtype_discipline(self, tmp_path):
        text = (REPO_ROOT / "src/repro/core/digitize.py").read_text()
        head, sep, tail = text.partition("def digitize_span_table(")
        needle = "lengths.astype(jnp.float32)"
        assert needle in tail
        tail = tail.replace(needle, "lengths.astype(jnp.float64)", 1)
        result = run(tmp_path, {"digitize_mut.py": head + sep + tail},
                     ["SL007"], deep=True)
        assert any(f.rule == "SL007" and "64-bit" in f.message
                   for f in result.findings), \
            [f.message for f in result.findings]

    def test_sl008_unaliasable_donation_fires_and_clean_passes(
            self, tmp_path):
        src = (
            "import functools\n"
            "import jax\n"
            "\n"
            "@functools.partial(jax.jit, donate_argnums=(0,))"
            "  # symlint: entry(shapes=f32[8] f32[8])\n"
            "def step_bad(state, x):\n"
            "    return state[:-1] + x[:-1]\n"
            "\n"
            "@functools.partial(jax.jit, donate_argnums=(0,))"
            "  # symlint: entry(shapes=f32[8] f32[8])\n"
            "def step_ok(state, x):\n"
            "    return state + x\n"
        )
        result = run(tmp_path, {"mod.py": src}, ["SL008"], deep=True)
        assert result.findings, "dropped donation not caught"
        assert all(f.rule == "SL008" and "step_bad" in f.message
                   for f in result.findings), \
            [f.message for f in result.findings]


class TestRepoSmoke:
    def test_head_is_clean(self):
        """The committed tree passes all five rules against its baseline."""
        paths = [REPO_ROOT / d for d in ("src", "examples", "benchmarks")
                 if (REPO_ROOT / d).is_dir()]
        project = load_project(REPO_ROOT, paths)
        baseline = Baseline(REPO_ROOT / ".symlint-baseline.json")
        result = analyze(project, None, baseline)
        assert result.parse_errors == []
        assert result.findings == [], [f.to_json() for f in result.findings]
        assert result.stale_baseline == []
        assert result.exit_code == 0

    def test_cli_list_rules(self, capsys):
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("SL001", "SL002", "SL003", "SL004", "SL005",
                    "SL006", "SL007", "SL008"):
            assert rid in out

    def test_update_baseline_refuses_todo_placeholder(
            self, tmp_path, capsys, monkeypatch):
        (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
        (tmp_path / "mod.py").write_text(SL002_BRANCH)
        monkeypatch.chdir(tmp_path)
        bpath = tmp_path / "bl.json"
        code = main(["mod.py", "--update-baseline",
                     "--baseline", str(bpath)])
        out = capsys.readouterr().out
        assert code == 1
        assert "placeholder" in out
        # a written justification satisfies the gate on the next update
        doc = json.loads(bpath.read_text())
        doc["entries"][0]["justification"] = "reviewed: fixture only"
        bpath.write_text(json.dumps(doc))
        code = main(["mod.py", "--update-baseline",
                     "--baseline", str(bpath)])
        assert code == 0

    def test_changed_mode_filters_to_diff(self, tmp_path, capsys,
                                          monkeypatch):
        (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
        (tmp_path / "old.py").write_text(SL002_BRANCH)

        def git(*args):
            subprocess.run(
                ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
                cwd=tmp_path, check=True, capture_output=True)

        git("init", "-q")
        git("add", ".")
        git("commit", "-qm", "init")
        (tmp_path / "new.py").write_text(SL002_CONCRETIZE)
        monkeypatch.chdir(tmp_path)
        code = main(["old.py", "new.py", "--changed", "--no-baseline"])
        out = capsys.readouterr().out
        assert code == 1
        assert "new.py" in out
        assert "old.py" not in out

    def test_cli_github_format_on_fixture(self, tmp_path, capsys, monkeypatch):
        (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
        (tmp_path / "mod.py").write_text(SL002_BRANCH)
        monkeypatch.chdir(tmp_path)
        code = main(["mod.py", "--format=github", "--no-baseline"])
        out = capsys.readouterr().out
        assert code == 1
        assert "::error file=mod.py" in out
