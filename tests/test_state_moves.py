"""Carried state moves between slots in one compiled program per step.

The serving layer keeps the carries of slotless stateful streams in a
per-lane device carry store and re-lays the slot-major state buffer with
one compiled program per step that changes the slot layout (none on the
identity fast path). These tests hold it to the eager row rebuild it
replaced, kept here as the reference (:func:`_eager_gather`): at every
dispatch the rows fed to the served slots, and after every commit every
parked carry, must match the reference bit for bit -- under seeded slot
churn, synchronous and pipelined, and through checkpoint, quarantine
rollback, resize, close and reset. They also pin when the program
compiles, that an empty state compiles none, and its layout on a mesh.
"""
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import EngineConfig, SNNConfig, init_snn
from repro.core import events as ev
from repro.core._api import RecoveryConfig
from repro.core.pipeline import BatchedClosedLoop
from repro.fleet import FaultInjector
from repro.serving import FairQuantumPolicy, StreamEngine
from repro.serving.stream import _FREE

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="module")
def cfg():
    return SNNConfig(height=32, width=32, time_bins=4, conv1_features=4,
                     conv2_features=8, hidden=32, num_classes=11)


@pytest.fixture(scope="module")
def params(cfg):
    return init_snn(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def pool():
    rng = np.random.default_rng(41)
    return [ev.synthetic_gesture_events(rng, k % 11, mean_events=400,
                                        height=32, width=32)
            for k in range(12)]


def _row(tree, j):
    return jax.tree_util.tree_map(lambda a: np.asarray(a[j]), tree)


def _assert_same_bits(a, b, what):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape and x.dtype == y.dtype, what
        assert x.tobytes() == y.tobytes(), what


def _eager_gather(lane, parked):
    """The reference: the slot buffer rebuilt eagerly row by row, as the
    serving layer did before the carry store. ``parked`` maps each
    slotless stream to its carry (one slice per leaf). Returns the state
    to feed in and a thunk applying the reference's park and un-park."""
    slots = list(lane.slots)
    pos = {owner: j for j, owner in enumerate(lane.state_streams)
           if owner is not _FREE}
    src = []
    for sid in slots:
        if sid is _FREE or sid not in lane.stateful:
            src.append(None)
        elif sid in pos:
            src.append(("row", pos[sid]))
        elif sid in parked:
            src.append(("parked", sid))
        else:
            src.append(None)
    identity = all(sid is _FREE or s == ("row", i)
                   for i, (sid, s) in enumerate(zip(slots, src)))
    if identity:
        state_in = lane.state
    else:
        leaves, treedef = jax.tree_util.tree_flatten(lane.state)
        zeros = jax.tree_util.tree_leaves(lane.zero_state)
        stacked = []
        for li, leaf in enumerate(leaves):
            rows = []
            for s in src:
                if s is None:
                    rows.append(zeros[li][0])
                elif s[0] == "row":
                    rows.append(leaf[s[1]])
                else:
                    rows.append(jax.tree_util.tree_leaves(parked[s[1]])[li])
            stacked.append(jnp.stack(rows))
        state_in = jax.tree_util.tree_unflatten(treedef, stacked)
    old_state, old_owners = lane.state, list(lane.state_streams)
    scheduled = {sid for sid in slots if sid is not _FREE}

    def commit():
        for j, owner in enumerate(old_owners):
            if owner is not _FREE and owner not in scheduled:
                parked[owner] = _row(old_state, j)
        for sid in scheduled:
            parked.pop(sid, None)

    return state_in, commit


class _Checked(StreamEngine):
    """A StreamEngine that checks every state move against the eager
    reference while it serves (sharing the reference's view of which
    carry each buffer row holds, which both keep the same way)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.ref = {}            # modality -> {stream: reference carry}
        self.programs = 0        # dispatches that ran the move program
        self.rollbacks = 0

    def ref_of(self, lane):
        return self.ref.setdefault(lane.modality, {})

    def check_parked(self, lane):
        ref = self.ref_of(lane)
        assert set(lane.parked) == set(ref)
        for sid, row in lane.parked.items():
            _assert_same_bits(_row(lane.store, row), ref[sid],
                              f"parked carry of {sid!r}")

    def _gather_state(self, lane):
        state_in, commit, programs = super()._gather_state(lane)
        want, ref_commit = _eager_gather(lane, self.ref_of(lane))
        # Rows of free slots are dead (their results are discarded), so
        # only the served rows are held to the reference.
        for i, sid in enumerate(lane.slots):
            if sid is not _FREE:
                _assert_same_bits(_row(state_in, i), _row(want, i),
                                  f"state_in row {i} ({sid!r})")
        self.programs += programs

        def checked(new_state):
            ref_commit()
            commit(new_state)
            self.check_parked(lane)

        return state_in, checked, programs

    def _rollback_carry(self, rec, sid):
        if rec.prev_carry is not None and sid in rec.prev_carry:
            state_in, slot = rec.prev_carry[sid]
            self.ref_of(rec.lane)[sid] = _row(state_in, slot)
            self.rollbacks += 1
        super()._rollback_carry(rec, sid)
        self.check_parked(rec.lane)

    def resize_lane(self, modality=None, *, slots, warm=True):
        lane = self._lane_named(modality)
        if lane.state is not None:
            for j, owner in enumerate(lane.state_streams):
                if owner is not _FREE and owner in lane.stateful:
                    self.ref_of(lane)[owner] = _row(lane.state, j)
        out = super().resize_lane(modality, slots=slots, warm=warm)
        self.check_parked(lane)
        return out

    def forget(self, handle):
        """Mirror ``close`` / ``reset_state`` of a stream."""
        self.ref_of(handle._lane).pop(handle.stream_id, None)
        self.check_parked(handle._lane)


def _churn(eng, pool, seed, *, steps=14, extra_every=5):
    """Seeded slot churn: each open stream submits with probability 1/2
    per step, so streams drain, lose their slot and stay parked for a
    few steps; stateful streams open cold mid-run; every fourth step
    only the first stream submits. Then everything drains. Returns the
    handles and the results."""
    rng = np.random.default_rng(seed)
    hs = [eng.open(stateful=True) for _ in range(4)]
    hs.append(eng.open(stateful=False))
    out = []
    for k in range(steps):
        if k and k % extra_every == 0:
            hs.append(eng.open(stateful=True))        # cold start
        for i, h in enumerate(hs):
            p = float(i == 0) if k % 4 == 3 else 0.5
            if not h.closed and rng.random() < p:
                h.submit(pool[int(rng.integers(len(pool)))])
        out += eng.step()
    out += eng.run()
    return hs, out


def _engine(params, cfg, depth, slots=3, **kw):
    return _Checked(params, cfg, EngineConfig(
        max_streams=slots, pipeline_depth=depth,
        policy=FairQuantumPolicy(2), **kw))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("depth", [0, 1], ids=["sync", "pipelined"])
def test_churn_matches_eager_rebuild(cfg, params, pool, depth, seed):
    """More streams than slots, free slots, cold starts, streams parked
    for several steps: every served row and every parked carry is the
    eager rebuild's, bit for bit, and each window is served once."""
    eng = _engine(params, cfg, depth)
    hs, out = _churn(eng, pool, seed)
    assert eng.programs > 0
    assert all(r.ok for r in out)
    assert sorted((r.stream_id, r.seq) for r in out) == sorted(
        (h.stream_id, k) for h in hs for k in range(h.next_seq))


@pytest.mark.parametrize("depth", [0, 1], ids=["sync", "pipelined"])
def test_checkpoint_close_reset_resize_match_eager_rebuild(
        cfg, params, pool, depth):
    """The rare paths that read or write parked carries: a checkpoint
    exports a parked carry as the reference holds it, and a restore,
    ``close`` and ``reset_state`` of parked streams and ``resize_lane``
    either way keep every carry the reference's."""
    eng = _engine(params, cfg, depth)
    hs, _ = _churn(eng, pool, seed=5, steps=8)
    lane = eng._lanes["event"]
    parked = [h for h in hs if h.stream_id in lane.parked]
    assert len(parked) >= 3
    ck = parked[0].checkpoint()
    _assert_same_bits(ck.state, eng.ref_of(lane)[parked[0].stream_id],
                      "checkpoint of a parked carry")
    parked[1].close()
    eng.forget(parked[1])
    parked[2].reset_state()
    eng.forget(parked[2])
    # Restore the checkpoint as a new stream of the same engine.
    moved = eng.restore(ck, stream_id="moved")
    payload = lane.engine.import_state(lane.engine.init_state(1), 0,
                                       ck.state)
    eng.ref_of(lane)["moved"] = _row(payload, 0)
    eng.check_parked(lane)
    rng = np.random.default_rng(6)
    live = [h for h in hs + [moved] if not h.closed]
    for slots in (5, 2, 3):
        for h in live:
            h.submit(pool[int(rng.integers(len(pool)))])
        eng.step()
        eng.resize_lane(slots=slots)
        eng.step()
    eng.run()
    assert eng.programs > 0


@pytest.mark.parametrize("depth,kind", [(0, "nan"), (1, "nan"),
                                        (1, "error")],
                         ids=["sync-nan", "pipelined-nan",
                              "pipelined-error"])
def test_quarantine_rollback_matches_eager_rebuild(cfg, params, pool,
                                                   depth, kind):
    """A poisoned or failed window rolls its stream's carry back into
    the store: the carry and every later move stay the reference's."""
    inj = FaultInjector()
    eng = _Checked(engines=[inj.wrap(BatchedClosedLoop(params, cfg))],
                   config=EngineConfig(
                       max_streams=3, pipeline_depth=depth,
                       policy=FairQuantumPolicy(2),
                       recovery=RecoveryConfig(max_retries=1,
                                               backoff_steps=0)))
    rng = np.random.default_rng(9)
    hs = [eng.open(stateful=True) for _ in range(5)]
    for k in range(10):
        if k in (3, 6):
            inj.fail_next("event", kind=kind)
        for h in hs:
            if rng.random() < 0.6:
                h.submit(pool[int(rng.integers(len(pool)))])
        eng.step()
    eng.run()
    assert eng.rollbacks > 0
    assert eng.programs > 0


def _move_compiles(cols):
    idx = np.flatnonzero(cols["name"] == "compile")
    return [i for i in idx if cols["parent"][i] == "state_gather"]


def test_move_program_compiles_once_per_capacity(cfg, params, pool):
    """The state-move program compiles once per lane, slot count and
    store capacity, as a ``compile`` span of value 1 inside the
    gather, and never again once warm; a store outgrown by new streams
    doubles, compiles once more and keeps every parked carry."""
    eng = _engine(params, cfg, depth=1, slots=2)
    most = max(w.num_events for w in pool)
    eng.warmup([(2, ev.next_pow2(most), 300_000)])
    lane = eng._lanes["event"]

    def serve(handles, rounds, since):
        for k in range(rounds):
            for i, h in enumerate(handles):
                h.submit(pool[(k + i) % len(pool)])
            eng.step()
        eng.run()
        return tracing.spans(since_ns=since)

    since = time.perf_counter_ns()
    hs = [eng.open(stateful=True) for _ in range(3)]
    cols = serve(hs, 4, since)
    first = _move_compiles(cols)
    assert len(first) == 1
    assert cols["value"][first[0]] == 1 and cols["lane"][first[0]] == "event"
    assert lane.capacity == 4 and set(lane.move_exe) == {(2, 4)}
    assert (cols["name"] == "compile").sum() == 1

    since = time.perf_counter_ns()
    cols = serve(hs, 4, since)
    assert not (cols["name"] == "compile").sum()
    assert cols["value"][cols["name"] == "state_gather"].max() == 1

    since = time.perf_counter_ns()
    hs += [eng.open(stateful=True) for _ in range(2)]
    cols = serve(hs, 3, since)
    assert len(_move_compiles(cols)) == 1
    assert lane.capacity == 8 and set(lane.move_exe) == {(2, 4), (2, 8)}


def test_empty_state_compiles_no_move_program():
    """The frame wing's state is the empty pytree: its stateful streams
    churn through the slots with no store program compiled or run."""
    from repro.core import FrameTCNEngine, TCNConfig, init_tcn
    from repro.core import frames as fr
    tcfg = TCNConfig(height=32, width=32, conv1_features=4,
                     conv2_features=8, hidden=32, num_classes=11)
    eng = StreamEngine(engines=[FrameTCNEngine(
        init_tcn(jax.random.PRNGKey(2), tcfg), tcfg)],
        config=EngineConfig(max_streams=2, policy=FairQuantumPolicy(1)))
    rng = np.random.default_rng(7)
    frames = [fr.synthetic_gesture_frames(rng, k, height=32, width=32)
              for k in range(3)]
    since = time.perf_counter_ns()
    hs = [eng.open(stateful=True) for _ in range(3)]
    for k in range(3):
        for h in hs:
            h.submit(frames[k])
    assert len(eng.run()) == 9
    cols = tracing.spans(since_ns=since)
    lane = eng._lanes["frame"]
    assert lane.parked and not lane.move_exe
    assert not _move_compiles(cols)
    gather = cols["name"] == "state_gather"
    assert gather.sum() and set(cols["value"][gather]) == {0}


_MESH_BODY = """
import numpy as np, jax
from jax.sharding import PartitionSpec as P
from repro.core import SNNConfig, init_snn
from repro.core import events as ev
from repro.core._api import EngineConfig
from repro.distributed import make_mesh
from repro.serving import FairQuantumPolicy, StreamEngine

CFG = SNNConfig(height=32, width=32, time_bins=4, conv1_features=4,
                conv2_features=8, hidden=32, num_classes=11)
PARAMS = init_snn(jax.random.PRNGKey(0), CFG)
rng = np.random.default_rng(3)
POOL = [ev.synthetic_gesture_events(rng, k % 11, mean_events=400,
                                    height=32, width=32) for k in range(8)]


class Recording(StreamEngine):
    def _gather_state(self, lane):
        state_in, commit, programs = super()._gather_state(lane)
        if programs:
            self.moved.append(state_in)
        return state_in, commit, programs


def serve(depth, mesh):
    eng = Recording(PARAMS, CFG, EngineConfig(
        max_streams=4, pipeline_depth=depth, mesh=mesh,
        policy=FairQuantumPolicy(2)))
    eng.moved = []
    hs = [eng.open(stream_id=f"s{i}", stateful=True) for i in range(7)]
    pick = np.random.default_rng(11)
    rows = {}
    for k in range(12):
        for h in hs:
            if pick.random() < 0.5:
                h.submit(POOL[int(pick.integers(len(POOL)))])
        for r in eng.step():
            rows[(r.stream_id, r.seq)] = np.asarray(r.result.logits)
    for r in eng.run():
        rows[(r.stream_id, r.seq)] = np.asarray(r.result.logits)
    return eng, rows


def same_bits(a, b):
    return np.asarray(a).tobytes() == np.asarray(b).tobytes()


for depth in (0, 1):
    base, want = serve(depth, None)
    shard, got = serve(depth, make_mesh(4))
    assert set(want) == set(got) and len(want) > 20
    for key in want:
        assert same_bits(want[key], got[key]), key
    lane, ref = shard._lanes["event"], base._lanes["event"]
    assert len(shard.moved) == len(base.moved) > 0
    for moved in shard.moved:
        for name, leaf in moved.items():
            assert leaf.sharding.spec[0] == "data", leaf.sharding
            assert leaf.sharding.is_equivalent_to(
                lane.zero_state[name].sharding, leaf.ndim)
    for a, b in zip(shard.moved, base.moved):
        for name in a:
            assert same_bits(a[name], b[name]), name
    assert lane.parked == ref.parked and lane.parked
    for name in lane.state:
        assert same_bits(lane.state[name], ref.state[name]), name
        for sid, row in lane.parked.items():
            assert same_bits(lane.store[name][row],
                             ref.store[name][row]), (name, sid)
print("OK")
"""


def test_mesh_moves_keep_slot_sharding_and_bits():
    """On four virtual devices, the moved state comes back with the
    engine's slot sharding (so the step neither reshards nor
    recompiles), and every served window, moved buffer and parked
    carry matches the unsharded engine bit for bit."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_MESH_BODY)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout, (out.stdout, out.stderr[-1500:])
