"""The events' wire shape (`drivers/train_jobs.wire_events`): a block without
`times` gives the dicts it always gave, one by one, so the three cells' stores
are the parent's; a block with `times` differs from that in `eventTime` alone,
and the store gives the times back.  Every cell of `BENCHMARK.json` is a case,
also one a later PR adds with a generator of its own (`test_third_engine.py`
runs this file in a tree with such a cell)."""

import json

import numpy as np
import pytest

from bench_helpers import ROOT, load_harness, rehearsal_config

H = load_harness()
DRIVER = H.load_module("drivers", "train_jobs")
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
EVENT_TIME = "2026-01-01T00:00:00+00:00"


def wire_events_of_pr29(block: dict):
    """The function as it stood before the newer keys (PR 23 to PR 29)."""
    name = block["event"]
    ratings = block.get("ratings")
    users, items = block["users"].tolist(), block["items"].tolist()
    for k in range(len(users)):
        d = {"event": name, "entityType": "user", "entityId": f"u{users[k]}",
             "targetEntityType": "item", "targetEntityId": f"i{items[k]}",
             "eventTime": EVENT_TIME}
        if ratings is not None:
            d["properties"] = {"rating": float(ratings[k])}
        yield d


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_a_cells_wire_dicts_are_the_parents_one_by_one(cell):
    config = rehearsal_config(H, cell)
    gen = H.load_module("data", config["data"]["generator"])
    data = gen.generate(config["data"]["params"], 2147483777)
    assert DRIVER.EVENT_TIME == EVENT_TIME
    n = 0
    for block in data["blocks"]:
        for new, old in zip(DRIVER.wire_events(block),
                            wire_events_of_pr29(block), strict=True):
            if "times" in block:        # no cell of PR 29 has them
                assert new.pop("eventTime") != old.pop("eventTime")
            # the same keys in the same order with the same values: the
            # same bytes on the wire and in the store's log
            assert json.dumps(new) == json.dumps(old)
            assert type(new.get("properties", {}).get("rating", 0.0)) is float
            n += 1
    assert n == sum(len(b["users"]) for b in data["blocks"]) > 0


def test_the_cells_of_pr29_have_no_times():
    """So the case above holds their stores to the parent's bytes whole."""
    for cell in ("ur-ecom-100k.train", "als-ml1m.train",
                 "ur-ecom-100k-u131k.train"):
        config = rehearsal_config(H, cell)
        gen = H.load_module("data", config["data"]["generator"])
        data = gen.generate(config["data"]["params"], 2147483777)
        assert not any("times" in b for b in data["blocks"])


BLOCK = {"event": "buy", "users": np.array([3, 3, 9]),
         "items": np.array([5, 6, 5])}


def test_times_are_microseconds_after_the_one_event_time():
    times = np.array([0, 61_000_001, 36 * 3600 * 10 ** 6], np.int64)
    got = list(DRIVER.wire_events({**BLOCK, "times": times}))
    assert [d["eventTime"] for d in got] == [
        "2026-01-01T00:00:00.000000+00:00", "2026-01-01T00:01:01.000001+00:00",
        "2026-01-02T12:00:00.000000+00:00"]
    for d, old in zip(got, wire_events_of_pr29(BLOCK)):
        assert {**d, "eventTime": EVENT_TIME} == old     # nothing else moves
    json.dumps(got)                                      # plain Python values


def test_ratings_are_the_property_rating_with_times_too():
    block = {**BLOCK, "ratings": np.array([1, 5, 3], np.float32),
             "times": np.array([5, 6, 7], np.int64)}
    assert [d["properties"] for d in DRIVER.wire_events(block)] == [
        {"rating": 1.0}, {"rating": 5.0}, {"rating": 3.0}]


def test_the_store_gives_back_the_times(tmp_path):
    """Through the ingest path a run uses (`insert_json_batch`) and the
    columnar read a DataSource uses (`PEventStore.batch`)."""
    from predictionio_tpu.storage import App
    from predictionio_tpu.storage.locator import Storage, StorageConfig
    from predictionio_tpu.store.event_store import PEventStore

    storage = Storage(StorageConfig(
        sources={"FS": {"type": "localfs", "path": str(tmp_path / "store")}},
        repositories={r: "FS" for r in ("METADATA", "EVENTDATA", "MODELDATA")}))
    app_id = storage.apps.insert(App(0, "bench"))
    times = np.array([7_200_000_000, 0, 1_500_000], np.int64)
    for r in storage.l_events.insert_json_batch(
            list(DRIVER.wire_events({**BLOCK, "times": times})), app_id):
        assert r.get("status") == 201, r
    buys = PEventStore.batch("bench", event_names=["buy"], storage=storage)
    base = int(np.datetime64("2026-01-01T00:00:00", "us").astype(np.int64))
    assert sorted(buys.times_us.tolist()) == sorted((base + times).tolist())
    assert {buys.entity_dict.str(int(c)) for c in buys.entity_ids} == {
        "u3", "u9"}
