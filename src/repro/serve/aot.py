"""AOT compilation + persisted executable cache for the serving loop.

A served :class:`repro.engine.Runner` dispatches a small, fully
enumerable set of staged steps (``Runner.aot_keys``).  This module lowers
each through the runner's existing audit surface —
``jax.jit(step).lower(*example_args).compile()`` over the concrete
arguments ``staged_steps()`` already builds — and installs the resulting
executables back into the shared step cache
(:meth:`~repro.engine.runner.Runner.install_executable`), so the first
real chunk is a cache hit: no tracing, no compile, no retrace recorded.

Persistence uses ``jax.experimental.serialize_executable``: each compiled
step serializes to ``(payload, in_tree, out_tree)`` (all picklable) keyed
by a structural fingerprint over everything the executable depends on —
query IR fingerprints, geometry, policy point, metrics mode, backend, jax
version and the version of the code the steps are staged from.  A fresh
process with a warm :class:`ExecutableCache` (plus a persisted plan
artifact for the seed shapes — see :mod:`repro.multiquery.shared`)
reaches first-result without tracing, planning or compiling anything.

The complementary :func:`enable_jax_compilation_cache` turns on jax's own
persistent compilation cache (HLO-hash keyed): it does not skip tracing,
but makes genuinely cold starts cheaper too.  Its directory is placed from
outside (``JAX_COMPILATION_CACHE_DIR``) or is one fixed path in the
checkout, so the same program finds its entries again.  The executable
cache is best-effort — a corrupt or stale entry degrades to a compile.
"""
from __future__ import annotations

import contextlib
import functools
import hashlib
import os
import pathlib
import pickle
import tempfile
from typing import Dict, Optional

import jax

from ..core import ir

__all__ = ["ExecutableCache", "aot_compile", "enable_jax_compilation_cache",
           "staged_code_version", "step_fingerprint"]

# the packages whose code is traced into the staged steps
_STAGED_PACKAGES = ("core", "engine", "kernels")


# the checkout's fixed compile-cache path: resolved from this file, never
# from the working directory, so every process of the repo shares it
DEFAULT_JAX_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[3] / "out" / "jax_cache")


def enable_jax_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    no other directory is set here; otherwise the cache goes to
    :data:`DEFAULT_JAX_CACHE_DIR`.  The min-size/min-time thresholds are
    dropped so CPU-scale entries qualify.  Failures raise."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_JAX_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


@contextlib.contextmanager
def _persistent_cache_off():
    """Compile with jax's persistent compilation cache bypassed.  An
    executable loaded from that cache does not survive
    ``serialize_executable`` on XLA:CPU (its kernels are missing once
    deserialized), so every executable this module persists is compiled
    afresh."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


def source_version(root, packages) -> str:
    """Hash of every ``.py`` file under ``root/<package>`` for each
    package, by relative path and content."""
    root = pathlib.Path(root)
    h = hashlib.sha256()
    for pkg in packages:
        for f in sorted((root / pkg).rglob("*.py")):
            h.update(f.relative_to(root).as_posix().encode() + b"\0")
            h.update(f.read_bytes())
    return h.hexdigest()


@functools.lru_cache(maxsize=None)
def staged_code_version() -> str:
    """Version of the code the staged steps are traced from (the
    ``core/``, ``engine/`` and ``kernels/`` sources), read once per
    process: an executable persisted by other code never loads."""
    return source_version(pathlib.Path(__file__).resolve().parents[1],
                          _STAGED_PACKAGES)


def _backend_tag() -> tuple:
    devs = jax.devices()
    return (jax.__version__, devs[0].platform, len(devs),
            devs[0].device_kind)


def step_fingerprint(runner, label: str, *,
                     query_fp: Optional[str] = None) -> str:
    """Process-stable content key of one staged step's executable: the
    query structure, the execution geometry (the staging-key DOFs with the
    mesh reduced to its shape), the metrics mode, the backend and the
    staged code's version.  Two processes that would compile
    byte-equivalent steps agree on it; any drift (new jax, different
    device count, changed geometry, an edited step body) misses."""
    spec = runner.spec
    if query_fp is None:
        if spec.roots:
            query_fp = "|".join(ir.fingerprint(r) for r in spec.roots)
        else:
            # opaque body: fall back to the planning artifacts (pure-data
            # dataclass reprs are deterministic)
            query_fp = repr((sorted(spec.input_specs.items()),
                             spec.change_plan))
    p = runner.policy
    payload = repr((query_fp, label, spec.out_len, spec.out_prec,
                    sorted(spec.out_precs.items()), spec.solo,
                    p.body, p.keys, p.dag,
                    p.axis if p.mesh is not None else None, p.n_shards,
                    runner.n_keys, runner.n_segs, runner.metrics.on,
                    runner.revision_horizon, _backend_tag(),
                    staged_code_version()))
    return hashlib.sha256(payload.encode()).hexdigest()


class ExecutableCache:
    """Directory of serialized step executables, one pickle per
    fingerprint: ``(payload, in_tree, out_tree, meta)`` as produced by
    ``jax.experimental.serialize_executable.serialize`` plus the step's
    donation contract.  Writes are atomic (tempfile + rename) so
    concurrent servers warming the same cache never read a torn entry."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        os.makedirs(self.path, exist_ok=True)

    def _file(self, fp: str) -> str:
        return os.path.join(self.path, f"{fp}.aotx")

    def has(self, fp: str) -> bool:
        return os.path.exists(self._file(fp))

    def load(self, fp: str):
        """``(loaded_executable, meta)`` or ``None`` on miss/corruption."""
        try:
            with open(self._file(fp), "rb") as f:
                payload, in_tree, out_tree, meta = pickle.load(f)
            from jax.experimental import serialize_executable as se
            return se.deserialize_and_load(payload, in_tree, out_tree), meta
        except FileNotFoundError:
            return None
        except Exception:
            # a torn/stale entry (interrupted writer, jax upgrade mid-key)
            # degrades to a compile, never an error
            try:
                os.remove(self._file(fp))
            except OSError:
                pass
            return None

    def store(self, fp: str, compiled, meta: Optional[dict] = None) -> None:
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = se.serialize(compiled)
        fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                pickle.dump((payload, in_tree, out_tree, dict(meta or {})),
                            f)
            os.replace(tmp, self._file(fp))
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise


def aot_compile(runner, cache: Optional[ExecutableCache] = None, *,
                chunks: Optional[Dict] = None,
                query_fp: Optional[str] = None) -> Dict[str, str]:
    """AOT-prepare every staged step ``runner`` dispatches.

    Warm path first: every persisted-cache hit installs its deserialized
    executable under the staging key *before* any step getter runs — a
    pre-populated cache slot is a hit, so the tracer records no compile
    (the warm-start proof) and the body is never traced.  Whatever is
    still missing is then staged normally, lowered against the runner's
    own concrete example arguments (``staged_steps()``), compiled, swapped
    into the step cache in place of the lazy jit wrapper (so the first
    real chunk doesn't compile a second time through the jit path) and
    persisted.

    Returns ``{step label: "loaded" | "compiled"}``.
    """
    if not runner.spec.jit:
        raise ValueError("AOT serving needs a jitted body (spec.jit=True)")
    report: Dict[str, str] = {}
    if cache is not None:
        for label, key in runner.aot_keys():
            got = cache.load(step_fingerprint(runner, label,
                                              query_fp=query_fp))
            if got is not None:
                loaded, meta = got
                runner.install_executable(
                    key, loaded, label=label, how="loaded",
                    donate=meta.get("donate", ()))
                report[label] = "loaded"
    if len(report) == len(runner.aot_keys()):
        return report  # fully warm: zero staging work
    for step in runner.staged_steps(chunks):
        label = step["label"]
        if label in report:
            continue
        lowered = step["fn"].lower(*step["args"])
        if cache is None:
            compiled = lowered.compile()
        else:
            with _persistent_cache_off():
                compiled = lowered.compile()
        runner.install_executable(step["key"], compiled, label=label,
                                  how="compiled", donate=step["donate"])
        report[label] = "compiled"
        if cache is not None:
            cache.store(step_fingerprint(runner, label, query_fp=query_fp),
                        compiled, meta={"donate": tuple(step["donate"])})
    return report
