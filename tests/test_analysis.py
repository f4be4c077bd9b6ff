"""Static-hazard analyzer tests (DESIGN.md §15).

Four layers:

* per-rule fixtures — a positive, a negative, and a waiver per checker;
* ratchet semantics — a new finding fails, a stale baseline entry fails;
* the repo gate — ``src/repro`` must stay clean against the committed
  ``analysis_baseline.json`` (this is the tier-1 wrapper the CI job runs);
* regressions for the true positives the first analyzer run burned down
  (checked docid casts, the replica pad-slice host sync, the replica
  ``docs_format`` threading, the `_record_batch` early guard).
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro.analysis import (
    RULES,
    analyze_paths,
    analyze_source,
    diff_baseline,
    help_for,
    load_baseline,
    missing_help,
    write_baseline,
)
from repro.analysis.__main__ import main as analysis_main

REPO = Path(__file__).resolve().parents[1]


def rules_of(report):
    return sorted({f.rule for f in report.findings})


# ----------------------------------------------------------- rule fixtures


class TestRecompile:
    def test_value_branch_on_traced_param_fires(self):
        rep = analyze_source(
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnames=('k',))\n"
            "def f(x, y, k):\n"
            "    if y > 0:\n"
            "        return x * k\n"
            "    return x\n"
        )
        assert rules_of(rep) == ["RECOMPILE"]
        assert "y" in rep.findings[0].message

    def test_shape_branch_and_static_param_are_clean(self):
        rep = analyze_source(
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnames=('k',))\n"
            "def f(x, y, k):\n"
            "    if x.shape[0] > 4 and k:\n"
            "        return x + y\n"
            "    return x\n"
        )
        assert rep.findings == []

    def test_string_literal_into_nonstatic_param_fires(self):
        rep = analyze_source(
            "import jax\n"
            "@jax.jit\n"
            "def g(x, mode):\n"
            "    return x\n"
            "def use(x):\n"
            "    return g(x, 'fast')\n"
        )
        assert rules_of(rep) == ["RECOMPILE"]
        assert "mode" in rep.findings[0].message

    def test_string_into_static_argnames_is_clean(self):
        rep = analyze_source(
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnames=('mode',))\n"
            "def g(x, mode):\n"
            "    return x\n"
            "def use(x):\n"
            "    return g(x, mode='fast')\n"
        )
        assert rep.findings == []


class TestHostsync:
    HOT = (
        "import numpy as np\n"
        "import jax\n"
        "class InflightServer:\n"
        "    def step(self):\n"
        "        out = self.dispatch_quantum()\n"
        "        jax.block_until_ready(out)\n"
        "        host = np.asarray(out)\n"
        "        return host\n"
        "    def dispatch_quantum(self):\n"
        "        return 1\n"
    )

    def test_sync_and_materialize_in_hot_root_fire(self):
        rep = analyze_source(self.HOT)
        assert rules_of(rep) == ["HOSTSYNC"]
        msgs = " ".join(f.message for f in rep.findings)
        assert "jax.block_until_ready" in msgs and "np.asarray" in msgs

    def test_same_body_outside_hot_roots_is_clean(self):
        rep = analyze_source(self.HOT.replace("InflightServer", "Offline"))
        assert rep.findings == []

    def test_sync_inside_python_loop_fires_anywhere(self):
        rep = analyze_source(
            "import jax\n"
            "def train(xs):\n"
            "    for x in xs:\n"
            "        jax.device_get(x)\n"
        )
        assert rules_of(rep) == ["HOSTSYNC"]

    def test_waiver_suppresses_and_is_counted(self):
        waived = self.HOT.replace(
            "jax.block_until_ready(out)",
            "jax.block_until_ready(out)  # analysis: allow[HOSTSYNC]",
        ).replace(
            "host = np.asarray(out)",
            "host = np.asarray(out)  # analysis: allow[HOSTSYNC]",
        )
        rep = analyze_source(waived)
        assert rep.findings == []
        assert len(rep.waived) == 2

    def test_comment_block_waiver_covers_next_code_line(self):
        rep = analyze_source(
            "import jax\n"
            "def train(xs):\n"
            "    for x in xs:\n"
            "        # step timing is the point here\n"
            "        # analysis: allow[HOSTSYNC]\n"
            "        jax.device_get(x)\n"
        )
        assert rep.findings == [] and len(rep.waived) == 1


class TestNarrow:
    def test_unguarded_docid_cast_fires(self):
        rep = analyze_source(
            "import numpy as np\n"
            "def build(new_ids):\n"
            "    docs = new_ids.astype(np.int32)\n"
            "    return docs\n",
            path="core/fixture.py",
        )
        assert rules_of(rep) == ["NARROW"]

    def test_clipped_cast_and_unwatched_name_are_clean(self):
        rep = analyze_source(
            "import numpy as np\n"
            "def build(new_ids, arr):\n"
            "    docs = np.clip(new_ids, 0, 7).astype(np.int32)\n"
            "    lane = arr.astype(np.int32)\n"
            "    buf = np.zeros(4, dtype=np.int32)\n"
            "    return docs, lane, buf\n",
            path="core/fixture.py",
        )
        assert rep.findings == []

    def test_out_of_scope_module_is_clean(self):
        rep = analyze_source(
            "import numpy as np\n"
            "def build(new_ids):\n"
            "    docs = new_ids.astype(np.int32)\n"
            "    return docs\n",
            path="tools/fixture.py",
        )
        assert rep.findings == []


class TestObsguard:
    def test_unguarded_obs_call_fires(self):
        rep = analyze_source(
            "class S:\n"
            "    def drain(self):\n"
            "        self.obs.observe('x', 1)\n",
            path="serving/fixture.py",
        )
        assert rules_of(rep) == ["OBSGUARD"]

    def test_enabled_guard_and_early_return_are_clean(self):
        rep = analyze_source(
            "class S:\n"
            "    def drain(self):\n"
            "        if self.obs.enabled:\n"
            "            self.obs.observe('x', 1)\n"
            "    def record(self):\n"
            "        if not self.obs.enabled:\n"
            "            return\n"
            "        self.obs.count('y')\n",
            path="serving/fixture.py",
        )
        assert rep.findings == []


class TestArtifact:
    def test_bare_write_fires(self):
        rep = analyze_source(
            "import json\n"
            "def save(path, rows):\n"
            "    with open(path, 'w') as f:\n"
            "        json.dump(rows, f)\n",
            path="index_io/fixture.py",
        )
        assert rules_of(rep) == ["ARTIFACT"]

    def test_staged_replace_is_clean(self):
        rep = analyze_source(
            "import json, os\n"
            "def save(path, rows):\n"
            "    with open(path + '.tmp', 'w') as f:\n"
            "        json.dump(rows, f)\n"
            "    os.replace(path + '.tmp', path)\n",
            path="index_io/fixture.py",
        )
        assert rep.findings == []


class TestPallasconst:
    def test_python_branch_on_ref_fires(self):
        rep = analyze_source(
            "def scatter_kernel(ref, out_ref):\n"
            "    if ref[0] > 0:\n"
            "        out_ref[0] = 1\n",
            path="kernels/fixture.py",
        )
        assert rules_of(rep) == ["PALLASCONST"]

    def test_nonstatic_grid_param_fires(self):
        rep = analyze_source(
            "import jax\n"
            "from functools import partial\n"
            "import jax.experimental.pallas as pl\n"
            "def body_kernel(x_ref, o_ref):\n"
            "    o_ref[0] = x_ref[0]\n"
            "@partial(jax.jit, static_argnames=('tile',))\n"
            "def run(x, n, tile):\n"
            "    return pl.pallas_call(body_kernel, grid=(n,))(x)\n",
            path="kernels/fixture.py",
        )
        assert any(
            f.rule == "PALLASCONST" and "grid" in f.message
            for f in rep.findings
        )

    def test_static_grid_and_pl_when_are_clean(self):
        rep = analyze_source(
            "import jax\n"
            "from functools import partial\n"
            "import jax.experimental.pallas as pl\n"
            "def body_kernel(x_ref, o_ref):\n"
            "    pl.when(x_ref[0] > 0)\n"
            "@partial(jax.jit, static_argnames=('tile',))\n"
            "def run(x, tile):\n"
            "    g = x.shape[0] // tile\n"
            "    return pl.pallas_call(body_kernel, grid=(g,))(x)\n",
            path="kernels/fixture.py",
        )
        assert rep.findings == []


# -------------------------------------------------------- ratchet semantics


class TestBaselineRatchet:
    BAD = (
        "import numpy as np\n"
        "def build(new_ids):\n"
        "    docs = new_ids.astype(np.int32)\n"
        "    return docs\n"
    )

    def findings(self):
        return analyze_source(self.BAD, path="core/fixture.py").findings

    def test_pinned_finding_passes(self, tmp_path):
        f = self.findings()
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), f)
        new, stale = diff_baseline(f, load_baseline(str(bl)))
        assert new == [] and stale == []

    def test_new_finding_fails(self, tmp_path):
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), [])
        new, stale = diff_baseline(self.findings(), load_baseline(str(bl)))
        assert len(new) == 1 and stale == []

    def test_stale_baseline_entry_fails(self, tmp_path):
        bl = tmp_path / "baseline.json"
        write_baseline(str(bl), self.findings())
        new, stale = diff_baseline([], load_baseline(str(bl)))
        assert new == [] and len(stale) == 1

    def test_key_survives_line_drift(self):
        shifted = "# a new comment line\n\n" + self.BAD
        a = self.findings()
        b = analyze_source(shifted, path="core/fixture.py").findings
        assert [f.key for f in a] == [f.key for f in b]
        assert a[0].line != b[0].line

    def test_cli_check_baseline_roundtrip(self, tmp_path, monkeypatch):
        pkg = tmp_path / "core"
        pkg.mkdir()
        (pkg / "fixture.py").write_text(self.BAD)
        monkeypatch.chdir(tmp_path)
        assert analysis_main(["check", "core"]) == 1
        assert analysis_main(["baseline", "core", "--out", "b.json"]) == 0
        assert analysis_main(["check", "core", "--baseline", "b.json"]) == 0
        (pkg / "fixture.py").write_text("x = 1\n")  # debt paid -> stale pin
        assert analysis_main(["check", "core", "--baseline", "b.json"]) == 1

    def test_cli_json_report(self, tmp_path, monkeypatch):
        pkg = tmp_path / "core"
        pkg.mkdir()
        (pkg / "fixture.py").write_text(self.BAD)
        monkeypatch.chdir(tmp_path)
        analysis_main(["check", "core", "--json", "rep.json"])
        rep = json.loads((tmp_path / "rep.json").read_text())
        assert rep["count"] == 1 and rep["by_rule"] == {"NARROW": 1}


# ------------------------------------------------------- catalog discipline


def test_every_rule_has_help_text():
    # Same no-empty-help bar as obs/catalog.py (test_obs.py).
    assert missing_help() == []
    assert len(RULES) >= 6


def test_explain_cli_covers_every_rule(capsys):
    assert analysis_main(["explain"]) == 0
    out = capsys.readouterr().out
    for name in RULES:
        assert name in out
    assert help_for("narrow")  # case-insensitive lookup
    assert analysis_main(["explain", "NOSUCHRULE"]) == 2


# ------------------------------------------------------------ the repo gate


def test_repo_is_clean_against_committed_baseline():
    """The tier-1 wrapper: src/repro gated on analysis_baseline.json.

    Fails on any new finding AND on any stale pinned entry, so both
    regressions and silently-paid debt surface here (and in CI).
    """
    rep = analyze_paths([str(REPO / "src" / "repro")], rel_to=str(REPO))
    baseline = load_baseline(str(REPO / "analysis_baseline.json"))
    new, stale = diff_baseline(rep.findings, baseline)
    assert not new, "new findings:\n" + "\n".join(f.render() for f in new)
    assert not stale, "stale baseline keys:\n" + "\n".join(stale)
    # The burn-down left real waivers behind; the count only shrinks by
    # deleting the waived code, never by accident.
    assert len(rep.waived) >= 8


# ------------------------------------------------- perf-gate lint ratchet


def test_perf_gate_fails_when_finding_count_rises():
    from benchmarks.perf_gate import gate

    hist = [{"static_findings": {"count": 1}}]
    fresh = {
        "headlines": {},
        "static_findings": {"count": 3, "by_rule": {"NARROW": 3}},
    }
    _soft, hard = gate(fresh, hist)
    assert any("static_findings" in h for h in hard)

    _soft, hard = gate(
        {"headlines": {}, "static_findings": {"count": 1}}, hist
    )
    assert hard == []
    _soft, hard = gate(
        {"headlines": {}, "static_findings": {"count": 0}}, hist
    )
    assert hard == []  # burning debt down is always fine

    _soft, hard = gate({"headlines": {}}, [{"obs": {}}])
    assert hard == []  # no recorded counts on either side -> nothing to gate


# ------------------------------------- regressions for burned-down findings


class TestCheckedInt32:
    def test_raises_past_int32(self):
        from repro.core.bm25 import checked_int32

        with pytest.raises(OverflowError):
            checked_int32(np.array([0, 2**31], dtype=np.int64), "docids")
        with pytest.raises(OverflowError):
            checked_int32(np.array([-1], dtype=np.int64), "docids")

    def test_matches_plain_cast_in_range(self):
        from repro.core.bm25 import checked_int32

        rng = np.random.default_rng(7)
        a = rng.integers(0, 2**31 - 1, size=512, dtype=np.int64)
        np.testing.assert_array_equal(checked_int32(a), a.astype(np.int32))
        assert checked_int32(a).dtype == np.int32


def test_record_batch_early_returns_when_obs_disabled():
    # Before the OBSGUARD fix this crashed (np.asarray(None)) — the guard
    # lived only at drain_once's call site.
    from repro.obs import NOOP
    from repro.serving.microbatch import MicroBatchServer

    stub = SimpleNamespace(obs=NOOP)
    assert (
        MicroBatchServer._record_batch(
            stub, None, None, None, None, None, None, None, None
        )
        is None
    )


def test_replica_pad_slice_stays_on_device():
    import jax
    import jax.numpy as jnp

    from repro.control.replica import _slice_pad

    full = (jnp.arange(6).reshape(3, 2), jnp.ones(3))
    out = _slice_pad(full, 2)
    for x, ref in zip(out, full):
        assert isinstance(x, jax.Array) and not isinstance(x, np.ndarray)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(ref)[:2])


def test_replica_mesh_dispatch_threads_docs_format(monkeypatch):
    # The replica mesh must serve a packed-docids index with the same
    # decode the wrapped engine uses; before the fix docs_format was
    # silently dropped and packed indexes decoded as int32.
    import repro.control.replica as replica_mod
    from repro.obs import NOOP

    captured = {}

    def fake_make_mesh_dispatch(mesh, axis, **kwargs):
        captured.update(kwargs)
        return lambda *a: ("out",)

    monkeypatch.setattr(
        replica_mod, "make_mesh_dispatch", fake_make_mesh_dispatch
    )
    monkeypatch.setattr(replica_mod, "place_index", lambda *a: (None, None))
    se = SimpleNamespace(
        n_shards=1, s_pad=4, k=8, impl="jax",
        docs_format="packed", dix=None, doc_base=None, obs=NOOP,
    )
    eng = replica_mod.ReplicaGroupEngine(se, n_replicas=1, use_mesh=True)
    blk = np.zeros((1, 2, 3), dtype=np.int32)
    z = np.zeros((1, 2), dtype=np.int32)
    eng.dispatch(blk, blk, z, z, z, z)
    assert captured["docs_format"] == "packed"
