"""Every kept Pallas kernel compiles for a TPU v5e chip at the main path's
geometries.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described (``topologies.get_topology_desc``), not attached.
Interpret mode cannot catch what this catches — block shapes off the
(8, 128) tiling, primitives Mosaic cannot lower (``cumsum``), kernels left
for the SPMD partitioner.  Each test asserts the kernel really is in the
compiled program (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import compile as qc
from repro.core.plan import seg_range_affine
from repro.data.apps import make_keyed_app
from repro.kernels import sparse_compact
from repro.kernels import window_reduce as wr


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with jax's persistent compilation cache
    off (a described-chip compile could be written to it but never read
    back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """One device of the described host."""
    return SingleDeviceSharding(topo.devices[0])


def _compiled_has_kernel(fn, one_chip, *shapes) -> bool:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _fraud_geometry(win: int, seg: int):
    """The (a0, step, width) lineage triple and per-key buffer length the
    runner's sparse step hands seg_dirty for the keyed fraud app."""
    exe = qc.compile_query(make_keyed_app("fraud", win=win).query.node,
                           out_len=seg, sparse=True)
    s, sp = exe.input_specs["in"], exe.change_plan.specs["in"]
    geom = seg_range_affine(sp.lookback, sp.lookahead, s.prec,
                            grid_t0=-s.left_halo * s.prec, out_t0=0,
                            out_prec=exe.out_prec, seg_len=seg)
    return geom, s.left_halo


# (win, seg, segs per chunk, keys): the one-chip smoke's deployment (fraud,
# window 1000, 8 × 256-tick segments, 4096 keys), short windows, and a
# segment stride that shares no power of two with the lane tile
SEG_DIRTY_CASES = [(1000, 256, 8, 4096), (1000, 256, 8, None),
                   (20, 64, 16, 512), (50, 32, 32, 64),
                   (1000, 1000, 4, None)]


@pytest.mark.parametrize("win,seg,n_segs,keys", SEG_DIRTY_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_seg_dirty_compiles_for_v5e(one_chip, win, seg, n_segs, keys, dtype):
    geom, halo = _fraud_geometry(win, seg)
    T = halo + seg * n_segs

    def f(x):
        return sparse_compact._seg_dirty_pallas([x], [geom], n_segs, False)

    if keys is None:
        assert _compiled_has_kernel(f, one_chip, ((2, T), dtype))
    else:  # vmapped over the key axis, as the runner calls it
        assert _compiled_has_kernel(jax.vmap(f), one_chip,
                                    ((keys, 2, T), dtype))


# (window, channels, ticks per unit, units): per-unit window reductions as
# the keyed runner vmaps them (K · segs units of seg + halo ticks), plus a
# single long stream
WINDOW_CASES = [(1000, 3, 256 + 1000, 4096 * 8), (50, 3, 32 + 50, 2048),
                (20, 2, 64 + 20, 8192), (1000, 2, 16384, None)]
COMBINES = {"sum": (jnp.add, 0.0), "max": (jnp.maximum, -jnp.inf),
            "min": (jnp.minimum, jnp.inf)}


@pytest.mark.parametrize("win,C,T,units", WINDOW_CASES)
@pytest.mark.parametrize("op", sorted(COMBINES))
def test_sliding_assoc_compiles_for_v5e(one_chip, win, C, T, units, op):
    combine, ident = COMBINES[op]

    def f(x):
        return wr.sliding_assoc(x, win, combine, ident, interpret=False)

    shape = (C, T) if units is None else (units, C, T)
    g = f if units is None else jax.vmap(f)
    assert _compiled_has_kernel(g, one_chip, (shape, jnp.float32))


@pytest.mark.parametrize("C,T,units", [(3, 1256, 4096 * 8), (2, 16384, None),
                                       (2, 40, 256)])
def test_prefix_scan_compiles_for_v5e(one_chip, C, T, units):
    def f(x):
        return wr.prefix_scan(x, interpret=False)

    shape = (C, T) if units is None else (units, C, T)
    g = f if units is None else jax.vmap(f)
    assert _compiled_has_kernel(g, one_chip, (shape, jnp.float32))


# the phase scopes the fused sparse step names, and the dense step's one
SPARSE_SCOPES = {"tilt.change_detect", "tilt.compact", "tilt.gather",
                 "tilt.compute", "tilt.scatter", "tilt.hold"}
STEP_CASES = [
    ("fraud", "sparse", {"sparse_fused(first)": "tilt_sparse_first",
                         "sparse_fused(steady)": "tilt_sparse_steady",
                         "obs_accum": "tilt_obs_accum"}),
    ("trend", "dense", {"dense": "tilt_dense_step"})]


@pytest.mark.parametrize("app,body,names", STEP_CASES)
def test_staged_steps_carry_names_and_scopes_on_v5e(one_chip, monkeypatch,
                                                    app, body, names):
    """The served runner's staged steps, compiled for the described chip
    with the Pallas kernels in: each module is ``jit_<stable name>``, the
    phase scopes reach the ops' ``op_name`` metadata (what a device trace
    reads as ``tf_op``), the change-detection kernel is the custom call
    ``seg_dirty`` and the window kernels keep their ``jit_sliding_*``
    names."""

    from repro.engine import ExecPolicy, Runner
    from repro.kernels import ops
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    args = {"win": 1000} if app == "fraud" else {}
    exe = qc.compile_query(make_keyed_app(app, **args).query.node,
                           out_len=256, sparse=body == "sparse")
    runner = Runner(exe, ExecPolicy(body=body, keys="vmapped"), n_keys=8,
                    segs_per_chunk=4)
    steps = runner.staged_steps()
    assert {s["label"] for s in steps} == set(names)
    for step in steps:
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), step["args"])
        text = step["fn"].lower(*shapes).compile().as_text()
        assert text.startswith(f"HloModule jit_{names[step['label']]},")
        scopes = set(re.findall(r'op_name="[^"]*?(tilt\.[a-z_]+)', text))
        kernels = re.findall(r"(%\S+) = .*custom_call_target="
                             r'"tpu_custom_call"', text)
        if step["label"] == "obs_accum":
            assert not kernels
            continue
        window = [k for k in kernels if "seg_dirty" not in k]
        assert window and all(re.search(r"jit_sliding_(sum|assoc)", k)
                              for k in window), kernels
        if body == "dense":
            assert scopes == {"tilt.compute"}
        else:
            assert scopes == SPARSE_SCOPES
            assert [k for k in kernels if "seg_dirty" in k], kernels


@pytest.mark.parametrize("label,name", [
    ("sparse_fused(steady)", "tilt_sparse_steady"),
    ("revise", "tilt_revision_step")])
def test_unit_window_gather_moves_whole_windows_on_v5e(one_chip, monkeypatch,
                                                       label, name):
    """The unit-window gather (scope ``tilt.gather``) of the steps that
    compact, compiled for the described chip at fraud's geometry: each
    compacted branch gathers whole windows (a slice of at least the window's
    length per index, never one tick per ``(key, tick)`` pair), no loop of
    per-unit slices stands in for a gather, and the full-capacity branch
    gathers nothing (its windows are static slices)."""
    import math

    from repro.core import sparse as sp
    from repro.engine import ExecPolicy, Runner
    from repro.kernels import ops
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    exe = qc.compile_query(make_keyed_app("fraud", win=1000).query.node,
                           out_len=256, sparse=True)
    runner = Runner(exe, ExecPolicy(body="sparse", keys="vmapped"), n_keys=8,
                    segs_per_chunk=4)
    runner.enable_revision(1)
    (step,) = [s for s in runner.staged_steps() if s["label"] == label]
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        step["args"])
    text = step["fn"].lower(*shapes).compile().as_text()
    assert text.startswith(f"HloModule jit_{name},")
    length = min(s.length for s in runner.spec.input_specs.values())
    full = len(sp.capacity_ladder(runner.n_keys * runner.n_segs)) - 1
    gathers = re.findall(r" gather\(.*slice_sizes=\{([0-9,]*)\}"
                         r'.*op_name="([^"]*tilt\.gather[^"]*)"', text)
    assert gathers, "no compacted branch gathers its windows"
    for sizes, op in gathers:
        assert math.prod(int(n) for n in sizes.split(",")) >= length, (
            sizes, op)
        assert f"/branch_{full}_fun/" not in op, op
    assert not re.findall(r' while\(.*op_name="[^"]*tilt\.gather', text)


COLLECTIVE = re.compile(r"\b(all-gather|all-reduce|collective-permute|"
                        r"all-to-all|reduce-scatter)(-start)?\(")


def test_keyed_mesh_sparse_steps_hold_no_collective_on_v5e(topo,
                                                           monkeypatch):
    """The keyed sparse steps under mesh placement, first and steady,
    compiled for all four devices of the described host at ``fraud_x4``'s
    per-key geometry (window 1000, 8 × 256-tick segments; 16 cards a
    chip): keys never communicate, so no cross-chip collective may appear
    anywhere in a step — change detection and the compute sit inside
    ``shard_map``, the hold fill is partitioned by key — and each device's
    program still runs the change-detection kernel."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.engine import ExecPolicy, Runner
    from repro.kernels import ops
    monkeypatch.setattr(ops, "use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    assert mesh.devices.size == 4
    exe = qc.compile_query(make_keyed_app("fraud", win=1000).query.node,
                           out_len=256, sparse=True)
    keys = 64
    # argument shapes from a local twin (every leaf leads with the key axis)
    twin = Runner(exe, ExecPolicy(body="sparse", keys="vmapped"), n_keys=4,
                  segs_per_chunk=8)
    runner = Runner(exe, ExecPolicy(body="sparse", keys="vmapped",
                                    placement=mesh),
                    n_keys=keys, segs_per_chunk=8)
    split = NamedSharding(mesh, P("data"))
    for step in twin.staged_steps():
        if not step["label"].startswith("sparse_fused"):
            continue
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((keys,) + x.shape[1:], x.dtype,
                                           sharding=split), step["args"])
        fn = runner._fused_sparse_step(step["label"].endswith("(first)"))
        text = fn.lower(*shapes).compile().as_text()
        assert not COLLECTIVE.findall(text), (
            step["label"], [ln.strip()[:200] for ln in text.splitlines()
                            if COLLECTIVE.search(ln)])
        assert "seg_dirty" in text and "tpu_custom_call" in text
