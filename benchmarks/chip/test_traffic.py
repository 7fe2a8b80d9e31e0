"""Traffic files state their whole mix, and every seed sends the same work.

    python -m pytest benchmarks/chip/test_traffic.py -q

Run by hand (seconds, numpy only).
"""
import copy
import json
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))

from gen.traffic import KINDS, Objects, check, make_plan  # noqa: E402

FILES = sorted((CHIP_DIR / "traffic").glob("*.json"))
LIVE = json.loads((CHIP_DIR / "traffic" / "live-mix.json").read_text())


@pytest.mark.parametrize("path", FILES, ids=[p.stem for p in FILES])
def test_every_traffic_file_states_its_whole_mix(path):
    check(json.loads(path.read_text()), path.stem)


@pytest.mark.parametrize("edit", [
    lambda t: t.pop("insert_jitter"),
    lambda t: t["queries"].pop("region_frac"),
    lambda t: t["mix"].pop("knn"),
    lambda t: t.update(insert_jiter=0.02),
    lambda t: t["geofences"].update(maxkeywords=3),
], ids=["missing", "missing_in_group", "missing_share", "misspelt", "misspelt_in_group"])
def test_a_key_missing_or_unknown_is_refused(edit):
    t = copy.deepcopy(LIVE)
    edit(t)
    with pytest.raises(ValueError, match="traffic 'live-mix'"):
        check(t, "live-mix")


def test_every_seed_sends_the_same_work():
    rng = np.random.default_rng(0)
    objs = Objects(rng.uniform(0, 1, (500, 2)).astype(np.float32),
                   rng.integers(0, 64, (500, 3)).astype(np.int32), 64)
    t = dict(LIVE, rate_per_s=40.0, geofences=dict(LIVE["geofences"], count=0))
    a, b = (make_plan(objs, t, 5.0, seed) for seed in (2**31 + 5, 2**33 + 7))
    for k in range(len(KINDS)):
        assert (a.kind == k).sum() == (b.kind == k).sum()
    np.testing.assert_allclose(np.sort(np.diff(a.due, prepend=0)), np.sort(np.diff(b.due, prepend=0)))
    assert not np.array_equal(a.due, b.due) and not np.array_equal(a.skr_rects, b.skr_rects)
