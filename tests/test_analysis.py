"""hclint build-time verifier (hclib_tpu/analysis): seeded-bad kernels
produce the expected findings with concrete witnesses, clean kernels
produce none, and the verify-off path is bit-identical. Everything here
is host-only composition - no Pallas build, no Mosaic, no device run
(except the one bit-identity pair, which runs the fast interpreter)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from hclib_tpu.analysis import (
    AnalysisError,
    check_layout,
    check_migratable,
    check_tile_windows,
    classify_megakernel,
)
from hclib_tpu.device.descriptor import (
    DESC_WORDS, F_DEP, F_FN, F_SUCC0, NO_TASK, TaskGraphBuilder,
)
from hclib_tpu.device.forasync_tier import Slab, TileKernel, \
    make_forasync_megakernel
from hclib_tpu.device.megakernel import BatchSpec, Megakernel
from hclib_tpu.device.workloads import FIB, make_fib_megakernel
from hclib_tpu.runtime import env as envmod
from hclib_tpu.runtime.checkpoint import CheckpointBundle, CheckpointError

N, TS = 64, 8


def _specs():
    return {
        "x": jax.ShapeDtypeStruct((N,), jnp.int32),
        "y": jax.ShapeDtypeStruct((N,), jnp.int32),
    }


def _tile_kernel(store_index):
    return TileKernel(
        loads=[Slab("xin", "x", lambda a: (pl.ds(a[1], TS),), (TS,))],
        stores=[Slab("yout", "y", store_index, (TS,))],
        compute=lambda ins: {"yout": ins["xin"] * 3 + 7},
        data_specs=_specs(),
    )


# ------------------------------------------------------- tile windows


def test_tile_windows_clean():
    tk = _tile_kernel(lambda a: (pl.ds(a[1], TS),))
    rep = check_tile_windows(tk, [N], [TS])
    assert rep.findings == []


def test_tile_race_concrete_witness():
    """The planted bug: a store index ignoring the tile args - every
    tile writes window [0, TS). The witness names the two colliding
    tile coordinates."""
    tk = _tile_kernel(lambda a: (pl.ds(0, TS),))
    rep = check_tile_windows(tk, [N], [TS])
    assert len(rep.findings) == 1
    f = rep.findings[0]
    assert f.rule == "tile-race" and f.severity == "error"
    assert f.witness["tile_a"] == (0,) and f.witness["tile_b"] == (TS,)
    assert f.witness["window_a"] == ((0, TS),)


def test_tile_race_caught_at_construction():
    """Even without bounds, the synthetic-batch shim catches the same
    bug at Megakernel construction (slot-distinct args map to one
    window)."""
    tk = _tile_kernel(lambda a: (pl.ds(0, TS),))
    with pytest.raises(AnalysisError, match="batch-race"):
        make_forasync_megakernel(tk, width=4, interpret=True)


def test_clean_tile_kernel_constructs():
    tk = _tile_kernel(lambda a: (pl.ds(a[1], TS),))
    mk = make_forasync_megakernel(tk, width=4, interpret=True)
    assert mk.verify and mk.analysis is not None
    assert mk.analysis.errors() == []


# -------------------------------------------------- prefetch protocol


def _protocol_spec(body, drain):
    return BatchSpec(body, width=4, prefetch=True, drain=drain)


def _mk_with(spec, scratch):
    return Megakernel(
        kernels=[("k", lambda ctx: None)],
        route={"k": spec},
        data_specs=_specs(),
        scratch_specs=scratch,
        capacity=64, num_values=16, succ_capacity=8,
        interpret=True, verify=True,
    )


def _pf_scratch():
    from jax.experimental.pallas import tpu as pltpu

    return {
        "buf": pltpu.VMEM((2, 4, TS), jnp.int32),
        "sem": pltpu.SemaphoreType.DMA((2, 4)),
    }


def _start_loads(ctx, buf, s, base, wait):
    from jax.experimental.pallas import tpu as pltpu

    cp = pltpu.make_async_copy(
        ctx.data["x"].at[pl.ds(base, TS)],
        ctx.scratch["buf"].at[buf, s],
        ctx.scratch["sem"].at[buf, s],
    )
    (cp.wait if wait else cp.start)()


def _good_body(ctx):
    for s in range(ctx.width):
        @pl.when(ctx.live(s) & (jnp.int32(s) >= ctx.prefetched))
        def _(s=s):
            _start_loads(ctx, ctx.buf, s, ctx.arg(s, 1), wait=False)
    for s in range(ctx.width):
        @pl.when(jnp.int32(s) < ctx.prefetch_count)
        def _(s=s):
            _start_loads(ctx, 1 - ctx.buf, s, ctx.next_arg(s, 1),
                         wait=False)
    for s in range(ctx.width):
        @pl.when(ctx.live(s))
        def _(s=s):
            _start_loads(ctx, ctx.buf, s, ctx.arg(s, 1), wait=True)


def _good_drain(ctx):
    for s in range(ctx.width):
        @pl.when(jnp.int32(s) < ctx.prefetched)
        def _(s=s):
            _start_loads(ctx, ctx.buf, s, ctx.arg(s, 1), wait=True)


def test_prefetch_protocol_clean():
    mk = _mk_with(_protocol_spec(_good_body, _good_drain), _pf_scratch())
    assert mk.analysis.errors() == []


def test_prefetch_start_count_mismatch():
    """Planted bug: the body ignores ctx.prefetch_count (issues no
    prefetch starts) - the tier's announcement contract is violated."""

    def body(ctx):
        for s in range(ctx.width):
            @pl.when(ctx.live(s) & (jnp.int32(s) >= ctx.prefetched))
            def _(s=s):
                _start_loads(ctx, ctx.buf, s, ctx.arg(s, 1), wait=False)
        for s in range(ctx.width):
            @pl.when(ctx.live(s))
            def _(s=s):
                _start_loads(ctx, ctx.buf, s, ctx.arg(s, 1), wait=True)

    with pytest.raises(AnalysisError, match="no residual DMA starts"):
        _mk_with(_protocol_spec(body, _good_drain), _pf_scratch())


def test_prefetch_missing_drain():
    """Planted bug: a drain that retires nothing - the unmatched DMA
    start is the witness."""
    with pytest.raises(AnalysisError, match="never drained"):
        _mk_with(
            _protocol_spec(_good_body, lambda ctx: None), _pf_scratch()
        )


def test_unwaited_start_without_prefetch():
    """A non-prefetch batch body that starts a DMA and never waits it
    would let the copy outlive its completions."""

    def body(ctx):
        for s in range(ctx.width):
            @pl.when(ctx.live(s))
            def _(s=s):
                _start_loads(ctx, 0, s, ctx.arg(s, 1), wait=False)

    with pytest.raises(AnalysisError, match="never waited"):
        _mk_with(BatchSpec(body, width=4), _pf_scratch())


# --------------------------------------- approximate-trace demotion


def test_exact_window_finding_survives_truncated_loop():
    """The ISSUE 14 demotion fix, refusal side: an unmatched DMA WAIT
    that happens BEFORE any arg-dependent loop cannot have its missing
    start hidden in the skipped iterations - it stays an error even
    though the body also contains a truncated loop (the old blanket
    demotion would have silenced it)."""

    def body(ctx):
        _start_loads(ctx, 0, 0, ctx.arg(0, 1), wait=True)  # no start!
        jax.lax.fori_loop(0, ctx.arg(0, 0), lambda i, c: c, 0)

    with pytest.raises(AnalysisError, match="no matching start"):
        _mk_with(BatchSpec(body, width=4), _pf_scratch())


def test_truncation_dependent_finding_demotes_to_info():
    """Demotion side: an unmatched START whose matching wait could sit
    inside the truncated window (the cholesky arg-dependent-loop case)
    demotes to one info note - construction succeeds."""

    def body(ctx):
        _start_loads(ctx, 0, 0, ctx.arg(0, 1), wait=False)
        jax.lax.fori_loop(0, ctx.arg(0, 0), lambda i, c: c, 0)

    mk = _mk_with(BatchSpec(body, width=4), _pf_scratch())
    assert mk.analysis.errors() == []
    notes = [f for f in mk.analysis.findings
             if f.rule == "shim-unsupported"]
    assert notes and "truncated" in notes[0].message


# ------------------------------------------------- value-slot races


def test_blind_value_overwrite_is_a_race():
    """Planted bug: every slot's per-slot context clobbers value slot 3
    without reading it - slots 0..width-2's outputs are lost."""

    def body(ctx):
        for s in range(ctx.width):
            @pl.when(ctx.live(s))
            def _(s=s):
                ctx.slot_ctx(s).set_value(3, jnp.int32(s))

    with pytest.raises(AnalysisError, match="blind overwrite"):
        _mk_with(BatchSpec(body, width=4), {})


def test_sequential_accumulator_is_clean():
    """ptile-style read-modify-write on one shared slot is the
    legitimate sequential pattern (slots run in order)."""

    def body(ctx):
        for s in range(ctx.width):
            @pl.when(ctx.live(s))
            def _(s=s):
                k = ctx.slot_ctx(s)
                k.set_value(0, k.value(0) + 1)

    mk = _mk_with(BatchSpec(body, width=4), {})
    assert mk.analysis.errors() == []


# ------------------------------------------------------------- layout


def test_layout_table_clean():
    assert check_layout(force=True).findings == []


def test_layout_catches_drift(monkeypatch):
    from hclib_tpu.analysis import layout as lay

    bad = dict(lay.LAYOUT)
    bad["DESC_WORDS"] = (17, ("hclib_tpu.device.descriptor",))
    monkeypatch.setattr(lay, "LAYOUT", bad)
    rep = lay.check_layout(force=True)
    assert any(
        f.rule == "layout" and f.witness.get("word") == "DESC_WORDS"
        and f.witness.get("actual") == 16
        for f in rep.findings
    )
    # restore the memo for later tests
    monkeypatch.undo()
    assert lay.check_layout(force=True).findings == []


# ----------------------------------------------- classification/reshard


def test_classification_and_describe():
    mk = make_fib_megakernel(128, interpret=True)
    classes = classify_megakernel(mk)
    assert classes == {"fib": "home-linked", "sum": "link-free"}
    d = mk.describe()
    assert d["kinds"]["fib"]["classification"] == "home-linked"
    assert d["kinds"]["fib"]["dispatch"] == "scalar"
    assert d["verify"] is True


def test_a_row_rearmed_in_place_is_a_continuation_to_the_shim():
    """``ctx.become`` is a fork-join on the task's own row: a kind that
    re-arms and spawns only link-free children still leaves its links
    behind for a continuation, so it is home-linked, not link-free."""
    from hclib_tpu.analysis.classify import trace_class
    from hclib_tpu.analysis.shim import run_scalar_kernel

    def rearm_over_link_free_children(ctx):
        ctx.become(1, 1)
        ctx.spawn(2, [ctx.arg(0)], nargs=1)

    def only_link_free_children(ctx):
        ctx.spawn(2, [ctx.arg(0)], nargs=1)

    t = run_scalar_kernel(rearm_over_link_free_children, {}, {})
    assert t.continuations == 1
    assert trace_class(t) == "home-linked"
    t0 = run_scalar_kernel(only_link_free_children, {}, {})
    assert t0.continuations == 0 and trace_class(t0) == "link-free"


def test_migratable_audit_and_suppression():
    mk = make_fib_megakernel(128, interpret=True)
    rep = check_migratable(mk, [FIB], "test")
    assert [f.rule for f in rep.actionable()] == ["reshard-class"]
    assert rep.actionable()[0].witness["classification"] == "home-linked"
    # The workload's own annotation (verify_suppress on the builder)
    # marks the intent: finding present, not actionable.
    rep2 = check_migratable(mk, [FIB], "test", suppress=mk.verify_suppress)
    assert rep2.actionable() == []
    assert [f.suppressed for f in rep2.findings] == [True]


def _linked_bundle():
    ndev, cap, V = 2, 8, 4
    tasks = np.zeros((ndev, cap, DESC_WORDS), np.int32)
    tasks[:, :, F_DEP] = -1  # tombstones by default
    for d in range(ndev):
        for i in range(2):
            tasks[d, i, F_DEP] = 0
            tasks[d, i, F_FN] = 0
            tasks[d, i, F_SUCC0] = 1  # linked!
    counts = np.zeros((ndev, 8), np.int32)
    counts[:, 2] = 2  # alloc
    counts[:, 3] = 2  # pending
    counts[:, 4] = 2  # value_alloc
    arrays = {
        "tasks": tasks,
        "succ": np.full((ndev, 4), NO_TASK, np.int32),
        "ready": np.full((ndev, cap), NO_TASK, np.int32),
        "counts": counts,
        "ivalues": np.zeros((ndev, V), np.int32),
    }
    meta = {
        "kernel_names": ["fib", "sum"],
        "kind_classes": {"fib": "home-linked", "sum": "link-free"},
        "ndev": ndev,
    }
    return CheckpointBundle("resident", meta, arrays)


def test_reshard_upfront_whole_program_diagnostic():
    """The classification consumer: reshard refuses with ONE diagnostic
    naming every offending kind (with its build-time class and row
    count) instead of the first bad row."""
    with pytest.raises(CheckpointError) as ei:
        _linked_bundle().reshard(1)
    msg = str(ei.value)
    assert "4 live row(s)" in msg
    assert "'fib' [home-linked]: 4 row(s)" in msg
    assert "successor links" in msg  # the example row's reason


# ------------------------------------------------ off-path guarantees


def test_verify_off_is_bit_identical():
    """verify=False compiles the SAME program: identical lowered text,
    identical results - the verifier is pure host analysis and can only
    raise."""
    outs = {}
    texts = {}
    for v in (False, True):
        mk = make_fib_megakernel(128, interpret=True)
        mk2 = Megakernel(
            kernels=list(zip(mk.kernel_names, mk.kernel_fns)),
            capacity=128, num_values=mk.num_values, succ_capacity=64,
            interpret=True, uses_row_values=True, verify=v,
        )
        b = TaskGraphBuilder()
        b.add(FIB, args=[10], out=0)
        iv, _, _ = mk2.run(b)
        outs[v] = int(iv[0])
        b2 = TaskGraphBuilder()
        b2.add(FIB, args=[10], out=0)
        tasks, succ, ring, counts = b2.finalize(
            capacity=128, succ_capacity=64
        )
        texts[v] = str(
            jax.jit(mk2._build_raw(64)).lower(
                jnp.asarray(tasks), jnp.asarray(succ), jnp.asarray(ring),
                jnp.asarray(counts),
                jnp.zeros(mk2.num_values, jnp.int32),
            ).as_text()
        )
    assert outs[False] == outs[True] == 55
    assert texts[False] == texts[True]


def test_verifier_never_invokes_mosaic():
    """The analysis package must stay host-only: its sources never
    build a kernel (pallas_call) nor touch the Mosaic interpreter
    (InterpretParams) - the off-path guarantee that verification can
    never change compiled programs."""
    import os as _os

    import hclib_tpu.analysis as pkg

    d = _os.path.dirname(pkg.__file__)
    for fname in sorted(_os.listdir(d)):
        if not fname.endswith(".py"):
            continue
        with open(_os.path.join(d, fname)) as f:
            src = f.read()
        assert "pallas_call" not in src, fname
        assert "InterpretParams" not in src, fname
        for line in src.splitlines():
            if line.strip().startswith(("import ", "from ")):
                assert "mosaic" not in line.lower(), (fname, line)


# ----------------------------------------------------------- env gate


def test_verify_env_gate(monkeypatch):
    def build():
        return Megakernel(
            kernels=[("noop", lambda ctx: None)],
            capacity=16, num_values=8, succ_capacity=8, interpret=True,
        )

    monkeypatch.setenv("HCLIB_TPU_VERIFY", "0")
    assert build().verify is False
    monkeypatch.setenv("HCLIB_TPU_VERIFY", "1")
    assert build().verify is True
    monkeypatch.delenv("HCLIB_TPU_VERIFY")
    assert build().verify is True  # default-on under pytest


def test_suppression_at_construction():
    tk = _tile_kernel(lambda a: (pl.ds(0, TS),))
    spec = BatchSpec(
        tk.batch_body, width=4, prefetch=True, drain=tk.batch_drain,
        verify_suppress=("batch-race",),
    )
    mk = Megakernel(
        kernels=[(tk.name, lambda ctx: None)],
        route={tk.name: spec},
        data_specs=tk.data_specs,
        scratch_specs=tk.batch_scratch(4),
        capacity=64, num_values=16, succ_capacity=8,
        interpret=True, verify=True,
    )
    sup = [f for f in mk.analysis.findings if f.suppressed]
    assert sup and sup[0].rule == "batch-race"
    assert mk.analysis.errors() == []


# -------------------------------------------------------- env registry


def test_env_registry_typed_parsing(monkeypatch):
    monkeypatch.setenv("HCLIB_TPU_QUIESCE_STRIDE", "7")
    assert envmod.env_int("HCLIB_TPU_QUIESCE_STRIDE") == 7
    monkeypatch.setenv("HCLIB_TPU_QUIESCE_STRIDE", "zap")
    with pytest.raises(ValueError, match="HCLIB_TPU_QUIESCE_STRIDE"):
        envmod.env_int("HCLIB_TPU_QUIESCE_STRIDE")
    assert envmod.env_int(
        "HCLIB_TPU_QUIESCE_STRIDE", malformed=1
    ) == 1
    monkeypatch.setenv("HCLIB_TPU_METRICS", "0")
    assert envmod.env_bool("HCLIB_TPU_METRICS") is False
    monkeypatch.setenv("HCLIB_TPU_STATS", "0")
    assert envmod.env_flag("HCLIB_TPU_STATS") is True  # legacy wart
    # legacy alias resolution
    monkeypatch.delenv("HCLIB_TPU_WORKERS", raising=False)
    monkeypatch.setenv("HCLIB_WORKERS", "3")
    assert envmod.env_int("HCLIB_TPU_WORKERS") == 3
    # name built dynamically so the lint registry rule (which scans
    # string constants tree-wide) doesn't see a phantom knob
    with pytest.raises(KeyError, match="not in the hclib_tpu env"):
        envmod.env_int("HCLIB_TPU_" + "NOT_A" + "_KNOB")
    rows = envmod.registry_table()
    assert any(r[0] == "HCLIB_TPU_VERIFY" for r in rows)


def test_lint_env_rules(tmp_path):
    import importlib.util
    import os as _os

    spec = importlib.util.spec_from_file_location(
        "lintmod",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), "tools", "lint.py"),
    )
    lintmod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lintmod)
    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    reg = lintmod.registry_names(repo)
    assert "HCLIB_TPU_VERIFY" in reg and "HCLIB_WORKERS" in reg
    bad = tmp_path / "bad.py"
    phantom = "HCLIB_TPU_" + "NEW" + "_KNOB"
    bad.write_text(
        "import os\n"
        "x = os.environ.get('HCLIB_TPU_TRACE', '')\n"
        f"y = os.environ['{phantom}']\n"
        "os.environ['HCLIB_TPU_TRACE'] = '1'\n"  # write: legal
    )
    probs = lintmod._check_python(str(bad), bad.read_text(), repo, reg)
    msgs = [m for _, m in probs]
    assert sum("raw os.environ read" in m for m in msgs) == 2
    assert any(phantom in m for m in msgs)


def test_hclint_cli_tree_is_clean(tmp_path):
    """Acceptance: the whole in-repo builder set - the curated 13
    builders plus the frontier/tenant programs and the protocol
    explorer - audits clean via tools/hclint.py, and the --json-out
    artifact carries machine-readable findings + certificates."""
    import importlib.util
    import json as _json
    import os as _os
    import sys as _sys

    saved = _os.environ.get("HCLIB_TPU_VERIFY")
    tools = _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "tools")
    _sys.path.insert(0, tools)
    try:
        spec = importlib.util.spec_from_file_location(
            "hclintmod", _os.path.join(tools, "hclint.py")
        )
        hclint = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(hclint)
        out = str(tmp_path / "hclint-findings.json")
        assert hclint.main(["--json-out", out]) == 0
        doc = _json.load(open(out))
        assert "protocols" in doc and "tenants:front_door" in doc
        assert doc["frontier:fr_bfs"]["certificates"]["bfs"][
            "status"] == "certified"
        assert doc["forasync:jacobi2d"]["certificates"]["fa_tile"][
            "status"] == "certified"
        for sec in doc.values():
            for f in sec["findings"]:
                assert {"rule", "severity", "kernel", "message",
                        "witness"} <= set(f)
    finally:
        _sys.path.remove(tools)
        if saved is None:
            _os.environ.pop("HCLIB_TPU_VERIFY", None)
        else:
            _os.environ["HCLIB_TPU_VERIFY"] = saved


def test_lint_trace_table_rule(tmp_path):
    """The one-table-edit invariant, enforced: a TR_* tag without a
    TAG_NAMES row (or never decoded by timeline.py) is a lint
    violation; the live tree is clean."""
    import importlib.util
    import os as _os

    spec = importlib.util.spec_from_file_location(
        "lintmod2",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), "tools", "lint.py"),
    )
    lintmod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lintmod)
    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    assert lintmod.check_trace_tables(repo) == []
    # Seed a drifted copy: a new tag with no name row and no decode.
    fake = tmp_path
    (fake / "hclib_tpu" / "device").mkdir(parents=True)
    (fake / "tools").mkdir()
    (fake / "hclib_tpu" / "device" / "tracebuf.py").write_text(
        "TR_ROUND_BEGIN = 1\n"
        "TR_PHANTOM = 99\n"
        "SC_LOST = 42\n"
        "TAG_NAMES = {TR_ROUND_BEGIN: 'round_begin'}\n"
        "SC_NAMES = {}\n"
    )
    (fake / "tools" / "timeline.py").write_text(
        "import tracebuf as tb\n"
        "x = tb.TR_ROUND_BEGIN\n"
    )
    probs = lintmod.check_trace_tables(str(fake))
    msgs = [m for _p, _l, m in probs]
    assert any("TR_PHANTOM has no TAG_NAMES row" in m for m in msgs)
    assert any("TR_PHANTOM has no decode row" in m for m in msgs)
    assert any("SC_LOST has no SC_NAMES row" in m for m in msgs)
