import os
import sys
from pathlib import Path

# tests run on the single real CPU device (the dry-run sets its own flags in
# a separate process); keep compilation light
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True)
def _reset_warn_once():
    """Warn/log-once registries (dispatch's dropped-override warning, the
    autotune interpolation log) must not leak across tests: a test that
    asserts 'warns once' would otherwise pass or fail depending on which
    test dispatched first."""
    from repro.kernels import registry

    registry.reset_warnings()
    yield


class SnapshotLog(list):
    """Every row snapshot an engine took, in order: ``uid``, ``upto`` (the
    cursor), ``since`` (the previous snapshot's cursor, None for a
    request's first) and ``moved`` (bytes to the host).  ``cadence_bound``
    is what a snapshot ``snapshot_every`` positions past the previous may
    move: one block of that width, plus the cursors and scales."""

    def __init__(self, engine):
        super().__init__()
        from repro.models import cache as dcache
        row = dcache.snapshot_row(engine.cache, 0)
        slabs = [lo for lo in jax.tree.leaves(
            row, is_leaf=lambda x: isinstance(x, dcache.LinearKV))
            if isinstance(lo, dcache.LinearKV)]
        per_pos = sum((lo.k.nbytes + lo.v.nbytes) // lo.capacity
                      for lo in slabs)
        slab_bytes = sum(lo.k.nbytes + lo.v.nbytes for lo in slabs)
        head = sum(x.nbytes for x in jax.tree.leaves(row)) - slab_bytes
        self.cadence_bound = head + per_pos * engine.snapshot_every


@pytest.fixture
def snapshot_copies():
    """``attach(engine, whole=False)`` records every row snapshot
    ``engine`` takes in a :class:`SnapshotLog` and returns it.  With
    ``whole=True`` each snapshot is the whole-row ``snapshot_row`` copy
    instead: the reference the incremental copies are held to."""
    from repro.models import cache as dcache

    def attach(engine, whole=False):
        log = SnapshotLog(engine)
        copy = engine._rows.copy

        def logged(cache, i, upto, base=None, since=0):
            if whole:
                row = dcache.snapshot_row(cache, i)
                moved = sum(x.nbytes for x in jax.tree.leaves(row))
            else:
                row, moved = copy(cache, i, upto, base, since)
            log.append({"uid": engine.slots[i].req.uid, "upto": upto,
                        "since": None if base is None else since,
                        "moved": moved})
            return row, moved

        engine._rows.copy = logged
        return log

    return attach
