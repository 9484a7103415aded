"""The traffic generator: the same seed gives the same inputs."""
import numpy as np
import pytest

from bench.lib import traffic as tr
from bench.tests import smoke


@pytest.fixture(scope="module")
def mix():
    return smoke.cell("fusion_uav_p80").mix


@pytest.fixture(scope="module")
def nets():
    c = smoke.cell("fusion_uav_p80")
    return c.arch.sensors(c.config), c.config["window_us"]


def _arrays(pool):
    return ([a for w in pool.events for a in (w.x, w.y, w.t, w.p)]
            + [f.pixels for f in pool.frames])


def test_pool_is_deterministic_per_seed(mix, nets):
    big = 2 ** 31 + 12345
    a = tr.make_pool(big, mix, *nets)
    b = tr.make_pool(big, mix, *nets)
    for x, y in zip(_arrays(a), _arrays(b)):
        np.testing.assert_array_equal(x, y)
    c = tr.make_pool(big + 1, mix, *nets)
    assert any(not np.array_equal(x, y)
               for x, y in zip(_arrays(a), _arrays(c)))


def test_every_seed_gets_the_same_labels_and_shapes(mix, nets):
    a = tr.make_pool(1, mix, *nets)
    b = tr.make_pool(2, mix, *nets)
    assert [w.label for w in a.events] == [w.label for w in b.events]
    assert [f.pixels.shape for f in a.frames] == [
        f.pixels.shape for f in b.frames]


def test_events_stay_on_the_sensor_and_in_the_window(mix, nets):
    dvs, window_us = nets[0]["event"], nets[1]
    for w in tr.make_pool(3, mix, *nets).events:
        assert w.x.min() >= 0 and w.x.max() < dvs["width"]
        assert w.y.min() >= 0 and w.y.max() < dvs["height"]
        assert w.t.min() >= 0 and w.t.max() < window_us
        assert set(np.unique(w.p)) <= {0, 1}
        assert np.all(np.diff(w.t) >= 0)


def test_open_loop_arrivals_are_the_same_set_for_every_seed():
    a = np.sort(tr.phases_ms(5, 40, 300.0))
    b = np.sort(tr.phases_ms(2 ** 33 + 7, 40, 300.0))
    np.testing.assert_array_equal(a, b)
    assert a.min() > 0 and a.max() < 300.0
    assert not np.array_equal(tr.phases_ms(5, 40, 300.0),
                              tr.phases_ms(6, 40, 300.0))


def test_unknown_traffic_key_is_refused(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"loop": "open", "heads": 1, "burst": 3}')
    with pytest.raises(ValueError, match="unknown keys"):
        tr.load("bad", str(p))
