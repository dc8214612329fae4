"""A run with the timed path broken underneath must come out not
correct: the harness's look for a chip is skipped, the rest of the run is
as in a benchmark run.  One case per fault a cell can have:

* ``state_unchanged``: the step returns its carried state unchanged
  (the stream state stays at its start);
* ``half_batch``: half of the keys' events are left out of each chunk;
* ``answer_altered``: one answer per key and chunk is altered where it
  is produced (the first tick's flag flipped and its value moved);
* ``stale_hold`` (cells under the sparse body): part of the dirty-unit
  set is dropped where the step pins it (every odd segment of a chunk),
  so those units skip their compute and hold serves stale output.

The exchange between chips does not exist here: every cell runs on one
chip, and keys are independent."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.stream import SnapshotGrid
from repro.engine import runner as runner_mod

import run



def _state_unchanged(step):
    def broken(self, chunks):
        out = step(self, chunks)
        self._tails = {}
        if self._sparse is not None:
            self._sparse = {"dirty": {}, "prev": {}, "seed": {},
                            "started": False}
        return out
    return broken


def _half_batch(step):
    def broken(self, chunks):
        cut = {}
        for name, g in chunks.items():
            half = g.valid.shape[0] // 2
            cut[name] = SnapshotGrid(
                value=jnp.asarray(g.value).at[half:].set(0),
                valid=jnp.asarray(g.valid).at[half:].set(False),
                t0=g.t0, prec=g.prec)
        return step(self, cut)
    return broken


def _answer_altered(step):
    def broken(self, chunks):
        out = step(self, chunks)
        return SnapshotGrid(value=out.value.at[:, 0].add(1.0),
                            valid=out.valid.at[:, 0].set(~out.valid[:, 0]),
                            t0=out.t0, prec=out.prec)
    return broken


def _stale_hold(unit_flags):
    def broken(self, flags):
        flags = unit_flags(self, flags)
        even = jnp.arange(flags.shape[1]) % 2 == 0
        return flags & even[None, :]
    return broken


# fault -> (the Runner method it breaks, how)
FAULTS = {"state_unchanged": ("step", _state_unchanged),
          "half_batch": ("step", _half_batch),
          "answer_altered": ("step", _answer_altered),
          "stale_hold": ("_unit_flags", _stale_hold)}
SPARSE_ONLY = {"stale_hold"}


def _cases():
    for w in run.benchmark()["workloads"]:
        sparse = run.config(w["config"])["policy"]["body"] == "sparse"
        for fault in sorted(FAULTS):
            if sparse or fault not in SPARSE_ONLY:
                yield w["name"], fault


@pytest.mark.parametrize("cell,fault", list(_cases()))
def test_fault_is_not_correct(tiny, monkeypatch, cell, fault):
    method, breaks = FAULTS[fault]
    monkeypatch.setattr(runner_mod.Runner, method,
                        breaks(getattr(runner_mod.Runner, method)))
    out = tiny.run_cell(cell, 2**31 + 99, 1.0, False, jax.devices()[:1])
    assert out["readings"]["flags"] > 0
    assert not out["correct"], out["checks"]
