"""The traffic generator: a traffic kind is found by its file, its
parameters reach the engine, and the configurations it draws are fresh,
uniform and the same for the same seed."""
import numpy as np
import pytest

import cells  # noqa: F401  (puts the harness on the path)
import run
from surrogate import draw_configs

KMEANS = [7] * 6 + [23] * 6 + [14, 14, 6, 6]
SOBEL = [17, 17, 20, 20, 7]


@pytest.mark.parametrize("sizes", [KMEANS, SOBEL])
def test_draws_are_fresh_and_within_the_space(sizes):
    taken: set = set()
    rng = np.random.default_rng(2**40 + 5)
    a = draw_configs(rng, sizes, 5000, taken)
    b = draw_configs(rng, sizes, 5000, taken)
    both = np.concatenate([a, b])
    assert both.shape == (10000, len(sizes))
    assert (both >= 0).all() and (both < np.asarray(sizes)).all()
    assert len({tuple(r) for r in both.tolist()}) == 10000
    assert len(taken) == 10000


def test_the_same_seed_draws_the_same_configurations():
    one = draw_configs(np.random.default_rng([2**33, 4]), SOBEL, 300, set())
    two = draw_configs(np.random.default_rng([2**33, 4]), SOBEL, 300, set())
    other = draw_configs(np.random.default_rng([2**33 + 1, 4]), SOBEL, 300,
                         set())
    assert np.array_equal(one, two) and not np.array_equal(one, other)


def test_a_nearly_full_space_still_draws_fresh():
    taken: set = set()
    total = int(np.prod(SOBEL))
    got = draw_configs(np.random.default_rng(9), SOBEL, total - 10, taken)
    assert len({tuple(r) for r in got.tolist()}) == total - 10


def test_a_traffic_kind_is_found_by_its_file():
    for w in run.load_json(run.ROOT / "BENCHMARK.json")["workloads"]:
        kind = run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"),
                           w["name"])["traffic"]["kind"]
        assert (run.BENCH / "loops" / f"{kind}.py").is_file()
        loop = run.loop(kind)
        for hook in ("setup", "build", "prepare", "window", "e2e", "check"):
            assert callable(getattr(loop, hook))
    with pytest.raises(FileNotFoundError):
        run.loop("no_such_kind")


def test_eval_devices_of_the_traffic_file_reaches_the_engine(monkeypatch):
    loop = run.loop("wave")
    seen = {}

    class Stop(Exception):
        pass

    def fake(cfg, program, devices=1):
        seen["devices"] = devices
        raise Stop

    monkeypatch.setitem(loop.build.__globals__, "Surrogate", fake)
    for traffic, want in (({}, 1), ({"eval_devices": 4}, 4)):
        with pytest.raises(Stop):
            loop({}, traffic, 1, 1.0, None).build()
        assert seen["devices"] == want
