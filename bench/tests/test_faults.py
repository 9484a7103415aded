"""With the timed path broken underneath, a run comes out not correct.

Each fault is planted in the program under test for one run at smoke
size, past the harness's look for a chip; the comparison with the
reference has to catch it: an answer altered where it is produced, half
of each batch left out, the carried state never advanced (stateful
cells), and, for fused heads, the two wings fused with other weights
than the configuration's or paired one tick apart. The four-chip cell
shards the slot axis of a step that holds no collective (each chip
serves its own slots), so there is no exchange between chips to leave
out; its faults run on four virtual CPU devices."""
import dataclasses
import time

import numpy as np
import pytest

from bench.lib import cells, runner
from bench.tests import smoke

CELLS = smoke.CELLS


def _run(name):
    c = smoke.cell(name)
    return runner.run_cell(c, 2 ** 31 + 99, 1.0, False, time.perf_counter(),
                           chip=False)


def _alter_answers(monkeypatch):
    from repro.core.pipeline import BatchedClosedLoop
    collect = BatchedClosedLoop.infer_collect

    def altered(self, pending):
        out = []
        for r in collect(self, pending):
            if r is not None:
                logits = np.array(r.logits)
                logits[0, int(np.argmin(logits[0]))] += 5.0
                r = dataclasses.replace(
                    r, logits=logits, label_pred=np.argmax(logits, -1))
            out.append(r)
        return out

    monkeypatch.setattr(BatchedClosedLoop, "infer_collect", altered)


def _drop_half_the_batch(monkeypatch):
    from repro.core.pipeline import BatchedClosedLoop
    prepare = BatchedClosedLoop.prepare

    def halved(self, items, *, batch_size):
        batch = prepare(self, items, batch_size=batch_size)
        batch.valid[np.flatnonzero(batch.occupied)[::2]] = False
        return batch

    monkeypatch.setattr(BatchedClosedLoop, "prepare", halved)


def _freeze_state(monkeypatch):
    from repro.serving.stream import StreamEngine
    plan = StreamEngine._lane_state_in

    def frozen(self, lane):
        state_in, commit = plan(self, lane)
        if commit is None:
            return state_in, commit
        return state_in, lambda new_state: commit(lane.zero_state)

    monkeypatch.setattr(StreamEngine, "_lane_state_in", frozen)


def _reweigh_fusion(monkeypatch):
    from repro.serving import session
    late = session.late_logit_fusion
    monkeypatch.setattr(session, "late_logit_fusion", lambda: late(0.6, 0.4))


def _shift_pairing(monkeypatch):
    from repro.serving.session import FusionSession
    fuse = FusionSession._fuse

    def shifted(self, e, f):
        """Tick k fuses event window k with frame k - 1."""
        prev = self.__dict__.get("_prev_frame", f)
        self._prev_frame = f
        return fuse(self, e, prev)

    monkeypatch.setattr(FusionSession, "_fuse", shifted)


FUSED = [n for n in CELLS if cells.cell(n).mix["fusion"]]
FAULTS = [(n, "answer_altered", _alter_answers) for n in CELLS]
FAULTS += [(n, "half_batch_left_out", _drop_half_the_batch) for n in CELLS]
FAULTS += [(n, "state_unchanged", _freeze_state) for n in CELLS
           if cells.cell(n).mix["stateful"]]
FAULTS += [(n, "fusion_reweighed", _reweigh_fusion) for n in FUSED]
FAULTS += [(n, "pairing_shifted", _shift_pairing) for n in FUSED]


@pytest.mark.parametrize("name,fault,plant", FAULTS,
                         ids=[f"{n}-{f}" for n, f, _ in FAULTS])
def test_fault_makes_the_run_incorrect(monkeypatch, name, fault, plant):
    plant(monkeypatch)
    out = _run(name)
    assert out["correct"] is False, (fault, out["compared"])
