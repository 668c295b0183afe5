"""Native C++ event-log scanner tests: parity with the Python path, escape/
unicode handling, and throughput sanity."""

import datetime as dt
import json
import time

import numpy as np
import pytest

from predictionio_tpu.events.event import DataMap, Event
from predictionio_tpu.native import native_available, scan_segments
from predictionio_tpu.storage import App
from predictionio_tpu.store import PEventStore

pytestmark = pytest.mark.skipif(
    not native_available(), reason="g++ unavailable; native scanner not built"
)


def ts(h):
    return dt.datetime(2026, 1, 2, h, tzinfo=dt.timezone.utc)


def seed(fs_storage, n=500):
    app_id = fs_storage.apps.insert(App(0, "natapp"))
    rng = np.random.default_rng(9)
    events = []
    for k in range(n):
        events.append(Event(
            event="rate" if k % 3 else "view",
            entity_type="user", entity_id=f"u{k % 17}",
            target_entity_type="item", target_entity_id=f"i{k % 31}",
            properties=DataMap({"rating": float(k % 5 + 1)} if k % 3 else {}),
            event_time=ts(k % 23),
        ))
    # escape/unicode torture rows
    events.append(Event(event="rate", entity_type="user",
                        entity_id='u"quoted\\slash',
                        target_entity_type="item", target_entity_id="naïve—item",
                        properties=DataMap({"rating": 2.5, "note": "line\nbreak\tand \"q\""}),
                        event_time=ts(1)))
    fs_storage.l_events.insert_batch(events, app_id)
    return app_id


def test_native_matches_python_path(fs_storage):
    app_id = seed(fs_storage)
    nat = PEventStore.batch("natapp", storage=fs_storage)  # native fast path
    events = list(fs_storage.p_events.scan(app_id))
    assert len(nat) == len(events)
    # compare as multisets of tuples
    def key(e):
        return (e.event, e.entity_id, e.target_entity_id,
                int(e.event_time.timestamp() * 1e6))

    py_keys = sorted(key(e) for e in events)
    nat_keys = sorted(
        (nat.event_dict.str(int(nat.event_codes[r])),
         nat.entity_dict.str(int(nat.entity_ids[r])),
         nat.target_dict.str(int(nat.target_ids[r])) if nat.target_ids[r] >= 0 else None,
         int(nat.times_us[r]))
        for r in range(len(nat))
    )
    assert py_keys == nat_keys
    # unicode/escape row survived intact
    assert 'u"quoted\\slash' in nat.entity_dict.strings()
    assert "naïve—item" in nat.target_dict.strings()


def test_native_filters(fs_storage):
    seed(fs_storage)
    rate_only = PEventStore.batch("natapp", event_names=["rate"], storage=fs_storage)
    assert len(rate_only) > 0
    rate_code = rate_only.event_dict.id("rate")
    assert (rate_only.event_codes == rate_code).all()
    windowed = PEventStore.batch("natapp", start_time=ts(5), until_time=ts(10),
                                 storage=fs_storage)
    assert ((windowed.times_us >= int(ts(5).timestamp() * 1e6)) &
            (windowed.times_us < int(ts(10).timestamp() * 1e6))).all()


def test_native_ratings_parse(fs_storage):
    seed(fs_storage)
    batch = PEventStore.batch("natapp", event_names=["rate"], storage=fs_storage)
    finite = np.isfinite(batch.ratings)
    assert finite.all()
    assert set(np.unique(batch.ratings)).issubset({1.0, 2.0, 2.5, 3.0, 4.0, 5.0})


def test_tombstones_force_python_fallback(fs_storage):
    app_id = seed(fs_storage, n=50)
    some_event = next(iter(fs_storage.l_events.find(app_id, limit=1)))
    fs_storage.l_events.delete(some_event.event_id, app_id)
    batch = PEventStore.batch("natapp", storage=fs_storage)
    # deleted event must not appear even though the scanner can't see tombstones
    ids = [batch.entity_dict.str(int(i)) for i in batch.entity_ids]
    assert len(batch) == 50  # 51 seeded rows (incl torture row) minus 1 deleted


def test_malformed_lines_skipped(tmp_path):
    seg = tmp_path / "seg-00000.jsonl"
    good = {"event": "view", "entityType": "user", "entityId": "u1",
            "eventTime": "2026-01-01T00:00:00+00:00"}
    seg.write_text(
        json.dumps(good) + "\n" +
        "this is not json\n" +
        '{"event": "", "entityType": "user", "entityId": "u2"}\n' +  # empty verb
        json.dumps(good) + "\n"
    )
    batch = scan_segments([seg])
    assert len(batch) == 2


# -- full property columns (round-3 generalization) --------------------------


def test_property_columns_all_types(fs_storage):
    """The scanner parses the FULL property map into typed sparse columns:
    numbers, bools, strings, string lists; numeric list elements are
    stringified; nested objects/nulls are dropped without killing the line."""
    app_id = fs_storage.apps.insert(App(0, "propapp"))
    events = [
        Event(event="$set", entity_type="item", entity_id="i1",
              properties=DataMap({
                  "price": 9.5, "inStock": True,
                  "category": "books", "tags": ["a", "b", 3],
                  "nested": {"x": 1}, "nothing": None,
                  "releaseDate": "2026-03-01T00:00:00+00:00"}),
              event_time=ts(2)),
        Event(event="$set", entity_type="item", entity_id="i2",
              properties=DataMap({"price": 4, "category": "music"}),
              event_time=ts(3)),
        Event(event="buy", entity_type="user", entity_id="u1",
              target_entity_type="item", target_entity_id="i1",
              event_time=ts(4)),
    ]
    fs_storage.l_events.insert_batch(events, app_id)
    paths = fs_storage.p_events.segment_paths(app_id, None)
    batch = scan_segments(paths)
    pc = batch.prop_columns
    assert pc is not None
    assert set(pc) >= {"price", "inStock", "category", "tags",
                       "releaseDate", "nested", "nothing"}
    # reconstruct i1's values through value_at
    row_i1 = int(np.flatnonzero(
        batch.entity_ids == batch.entity_dict.id("i1"))[0])
    vals = {}
    for key, col in pc.items():
        j = np.flatnonzero(col.rows == row_i1)
        if len(j):
            vals[key] = col.value_at(int(j[0]))
    assert vals["price"] == 9.5 and vals["inStock"] is True
    assert vals["category"] == "books"
    assert vals["tags"] == ["a", "b", "3"]
    assert vals["releaseDate"].startswith("2026-03-01")
    assert vals["nested"] == {"x": 1}   # raw-JSON kind, decoded lazily
    assert vals["nothing"] is None


def test_native_fold_matches_python_aggregate(fs_storage):
    """aggregate_properties through the native columnar fold equals the
    pure-Python l_events fold: $set merge, $unset removal, $delete drop,
    eventTime ordering."""
    app_id = fs_storage.apps.insert(App(0, "foldapp"))
    events = [
        Event(event="$set", entity_type="item", entity_id="a",
              properties=DataMap({"p": 1, "q": "x"}), event_time=ts(1)),
        Event(event="$set", entity_type="item", entity_id="a",
              properties=DataMap({"p": 2}), event_time=ts(5)),
        Event(event="$unset", entity_type="item", entity_id="a",
              properties=DataMap({"q": None}), event_time=ts(6)),
        Event(event="$set", entity_type="item", entity_id="b",
              properties=DataMap({"cats": ["x", "y"]}), event_time=ts(2)),
        Event(event="$set", entity_type="item", entity_id="gone",
              properties=DataMap({"p": 9}), event_time=ts(2)),
        Event(event="$delete", entity_type="item", entity_id="gone",
              properties=DataMap({}), event_time=ts(3)),
        # out-of-order arrival: older $set lands AFTER the newer one in the
        # log but must lose the fold
        Event(event="$set", entity_type="item", entity_id="a",
              properties=DataMap({"p": 0}), event_time=ts(0)),
        Event(event="$set", entity_type="user", entity_id="u",
              properties=DataMap({"p": 7}), event_time=ts(1)),
    ]
    fs_storage.l_events.insert_batch(events, app_id)
    native = PEventStore.aggregate_properties("foldapp", "item", storage=fs_storage)
    python = fs_storage.l_events.aggregate_properties(app_id, "item")
    assert set(native) == set(python) == {"a", "b"}
    for k in native:
        assert dict(native[k]) == dict(python[k]), (k, native[k], python[k])
    assert dict(native["a"]) == {"p": 2}
    assert dict(native["b"]) == {"cats": ["x", "y"]}


def test_malformed_line_corpus(fs_storage, tmp_path):
    """Fuzz-ish corpus at the C++ boundary: malformed lines are skipped,
    well-formed ones survive, and nothing crashes."""
    good = [
        json.dumps({"event": "buy", "entityType": "user", "entityId": f"u{k}",
                    "targetEntityType": "item", "targetEntityId": f"i{k}",
                    "properties": {"rating": k * 0.5, "tags": ["t"]},
                    "eventTime": "2026-01-01T00:00:00+00:00"})
        for k in range(5)
    ]
    bad = [
        "",                                     # empty
        "not json at all",
        "{",                                    # truncated object
        '{"event": "x"',                        # unterminated
        '{"event": 42}',                        # wrong type for event
        '{"entityId": "u1"}',                   # missing event
        '{"event": "x", "entityId": "u1", "properties": {"k": }}',  # bad value
        '{"event": "x", "entityId": "u1", "eventTime": "garbage-date"}',
        '{"event": "x", "entityId": "u1", "properties": [1,2,]}',
        '{"event": "\\ud800", "entityId": "u1"}',  # lone surrogate
        '{"event": "x", "entityId": "u1", "properties": {"a": {"deep": [1, {"b": 2}]}}}',
    ]
    seg = tmp_path / "seg-fuzz.jsonl"
    lines = []
    for i, g in enumerate(good):
        lines.append(g)
        lines.extend(bad[i * 2:(i + 1) * 2])
    seg.write_text("\n".join(lines + bad) + "\n")
    batch = scan_segments([seg])
    # exactly the good lines with an 'event' and entityId survive (the
    # nested-props bad line IS structurally valid JSON → also survives)
    events = [batch.event_dict.str(int(c)) for c in batch.event_codes]
    assert events.count("buy") == 5
    assert len(batch) >= 5


def test_ur_trains_through_native_scan(fs_storage):
    """UR training on a segment-file backend ingests via the C++ scanner
    (interactions AND item properties) and serves field rules from them."""
    from predictionio_tpu.controller.engine import EngineParams
    from predictionio_tpu.models.universal_recommender import (
        UniversalRecommenderEngine, URQuery)
    from predictionio_tpu.models.universal_recommender.engine import (
        URAlgorithmParams, URDataSourceParams)

    app_id = fs_storage.apps.insert(App(0, "urnat"))
    rng = np.random.default_rng(13)
    events = []
    for u in range(20):
        mine = "e" if u < 10 else "b"
        for i in range(5):
            if rng.random() < 0.8:
                events.append(Event(event="buy", entity_type="user",
                                    entity_id=f"u{u}", target_entity_type="item",
                                    target_entity_id=f"{mine}{i}", event_time=ts(u % 20)))
    for pfx, cat in (("e", "electronics"), ("b", "books")):
        for i in range(5):
            events.append(Event(event="$set", entity_type="item",
                                entity_id=f"{pfx}{i}",
                                properties=DataMap({"category": cat}),
                                event_time=ts(1)))
    fs_storage.l_events.insert_batch(events, app_id)

    from predictionio_tpu.storage.locator import set_storage
    set_storage(fs_storage)
    try:
        engine = UniversalRecommenderEngine.apply()
        ep = EngineParams(
            data_source_params=URDataSourceParams(
                app_name="urnat", event_names=["buy"]),
            algorithm_params_list=[("ur", URAlgorithmParams(
                app_name="urnat", mesh_dp=1))],
        )
        models = engine.train(ep)
        pred = engine.predictor(ep, models)
        res = pred(URQuery(user="u2", num=3))
        assert res.item_scores
        filt = pred(URQuery(user="u2", num=3, fields=[
            {"name": "category", "values": ["books"], "bias": -1}]))
        assert all(s.item.startswith("b") for s in filt.item_scores)
    finally:
        set_storage(None)


def test_hostile_property_keys(tmp_path):
    """Lone-surrogate and embedded-NUL property keys neither crash the scan
    nor collide columns."""
    seg = tmp_path / "seg-keys.jsonl"
    seg.write_text("\n".join([
        json.dumps({"event": "buy", "entityType": "user", "entityId": "u1",
                    "properties": {"a": 1}}),
        '{"event": "buy", "entityType": "user", "entityId": "u2", '
        '"properties": {"\\ud800key": 2}}',
        '{"event": "buy", "entityType": "user", "entityId": "u3", '
        '"properties": {"a\\u0000b": 3, "a": 4}}',
    ]) + "\n")
    batch = scan_segments([seg])
    assert len(batch) == 3
    pc = batch.prop_columns
    # 'a' and 'a\x00b' stay distinct columns
    assert "a" in pc and "a\x00b" in pc
    assert len(pc["a"]) == 2 and len(pc["a\x00b"]) == 1
    assert len([k for k in pc if k.endswith("key")]) == 1


def test_fold_with_interaction_only_property_keys(fs_storage):
    """A property key that appears only on non-special events (e.g. price
    on buy) must not break aggregate_properties — its column is empty after
    the special-event filter."""
    app_id = fs_storage.apps.insert(App(0, "mixprops"))
    fs_storage.l_events.insert_batch([
        Event(event="buy", entity_type="user", entity_id="u1",
              target_entity_type="item", target_entity_id="i1",
              properties=DataMap({"price": 3.5}), event_time=ts(1)),
        Event(event="$set", entity_type="item", entity_id="i1",
              properties=DataMap({"category": "x"}), event_time=ts(2)),
    ], app_id)
    props = PEventStore.aggregate_properties("mixprops", "item", storage=fs_storage)
    assert dict(props["i1"]) == {"category": "x"}


def test_native_layout_matches_numpy():
    """The C++ counting layout equals the numpy staging (same chunk
    grouping, counts and in-chunk order is irrelevant to the consumer, but
    contents per chunk must match as multisets)."""
    from predictionio_tpu.native import layout_chunks

    rng = np.random.default_rng(17)
    n_users, chunk, n_chunks = 1000, 256, 4
    u = rng.integers(0, n_users, 5000).astype(np.int32)
    i = rng.integers(0, 300, 5000).astype(np.int32)
    out = layout_chunks(u, i, chunk, n_chunks)
    assert out is not None
    lu, it, cnt = out
    assert lu.shape == it.shape and lu.shape[0] == n_chunks
    assert cnt.sum() == 5000
    for b in range(n_chunks):
        c = int(cnt[b])
        sel = (u // chunk) == b
        want = sorted(zip((u[sel] % chunk).tolist(), i[sel].tolist()))
        got = sorted(zip(lu[b, :c].tolist(), it[b, :c].tolist()))
        assert got == want
        assert (lu[b, c:] == 0).all() and (it[b, c:] == 0).all()
    # invalid input fails LOUDLY (same contract as the numpy path)
    bad = np.array([chunk * n_chunks + 5], np.int32)
    with pytest.raises(ValueError):
        layout_chunks(bad, bad, chunk, n_chunks)
    with pytest.raises(ValueError):
        layout_chunks(np.array([-1], np.int32), np.array([0], np.int32),
                      chunk, n_chunks)
    with pytest.raises(ValueError):
        layout_chunks(u, i[:100], chunk, n_chunks)


def test_native_layout_perf_sanity():
    from predictionio_tpu.native import layout_chunks

    rng = np.random.default_rng(3)
    n = 2_000_000
    u = rng.integers(0, 100_000, n).astype(np.int32)
    i = rng.integers(0, 8192, n).astype(np.int32)
    t0 = time.perf_counter()
    out = layout_chunks(u, i, 32768, 4)
    dt = time.perf_counter() - t0
    assert out is not None and out[2].sum() == n
    assert dt < 2.0, f"native layout too slow: {dt:.2f}s for {n} events"


# -- byte ranges: the scan against the Python path, across range boundaries --


def _range_log(tmp_chan):
    """Three segments whose lines are longer than the forced ranges: ids with
    escapes and `\\u` surrogate pairs, `Z` and `+05:30` times with and
    without fractions, every property kind, ids first seen in the last
    lines of the last segment.  Event times rise with the line, so `find`'s
    time order is the file order."""
    ids = ["u0", "u1", 'u"quoted\\slash', "naïve—\U0001F600", "tab\tid", "u2"]
    props = [
        {"rating": 4.5},
        {"price": 3, "inStock": True, "category": "books"},
        {"tags": ["a", "b", 3, 2.5, True, None, {"x": 1}, [1]], "note": None},
        {"nested": {"x": [1, {"y": "z\"q"}]}, "category": "mußic"},
        {},
        {"rating": 2, "tags": [], "category": "books"},
    ]
    t0 = dt.datetime(2026, 1, 2, tzinfo=dt.timezone.utc)
    ist = dt.timezone(dt.timedelta(hours=5, minutes=30))
    lines = []
    for k in range(45):
        t = t0 + dt.timedelta(minutes=7 * k, microseconds=123456 * (k % 2))
        if k % 4 < 2:
            when = t.strftime("%Y-%m-%dT%H:%M:%S") + (
                f".{t.microsecond:06d}" if t.microsecond else "") + "Z"
        else:
            when = t.astimezone(ist).isoformat()
        d = {"eventId": f"{k:032x}", "creationTime": t0.isoformat(),
             "event": ("rate", "view", "$set")[k % 3], "entityType": "user",
             "entityId": ids[k % len(ids)] if k < 43 else f"late-user-{k}",
             "eventTime": when}
        if k % 3 != 2:
            d["targetEntityType"] = "item"
            d["targetEntityId"] = (f"i{k % 5}" if k < 43 else f"late-item-{k}")
        if k % 7:
            d["properties"] = props[k % len(props)]
        lines.append(json.dumps(d, separators=(",", ":"), sort_keys=True))
    # a key given twice keeps its last value, as Python's json reads it
    lines[20] = '{"entityId":"given-twice",' + lines[20][1:]
    assert any("\\ud83d\\ude00" in ln for ln in lines)     # a surrogate pair
    assert any("+05:30" in ln for ln in lines)
    tmp_chan.mkdir(parents=True, exist_ok=True)
    paths = [tmp_chan / f"seg-{s:05d}.jsonl" for s in range(3)]
    for s, path in enumerate(paths):
        path.write_text("".join(ln + "\n" for ln in lines[15 * s:15 * s + 15]))
    return paths


def _expected_value(v):
    """What `PropColumn.value_at` gives for a property the writer stored:
    list elements as strings, nulls and containers inside a list dropped."""
    if isinstance(v, list):
        return [("true" if e else "false") if isinstance(e, bool)
                else e if isinstance(e, str) else "%.17g" % e
                for e in v if not isinstance(e, (dict, list, type(None)))]
    return v


@pytest.mark.parametrize("n_threads,range_bytes",
                         [(1, 64), (3, 257), (16, 1000), (3, 0)])
def test_ranges_match_python_path(fs_storage, n_threads, range_bytes):
    """The scan over byte ranges is the Python path's batch: equal columns,
    every dictionary in the same ORDER, equal property columns, whatever
    the ranges and the threads; malformed and torn lines leave nothing."""
    from predictionio_tpu.native.scanner import _scan
    from predictionio_tpu.store.columnar import EventBatch

    app_id = fs_storage.apps.insert(App(0, "rangeapp"))
    fs_storage.l_events.init(app_id)
    chan = fs_storage.p_events._chan_dir(app_id, None)
    paths = _range_log(chan)
    assert fs_storage.p_events.segment_paths(app_id, None) == paths
    events = list(fs_storage.l_events.find(app_id))
    want = EventBatch.from_events(events)
    assert len(want) == 45

    # what the Python path cannot read and the scan drops: a malformed line
    # and an empty verb inside a segment, a torn last line on two of them
    body = paths[1].read_text().splitlines(keepends=True)
    body[7:7] = ["this is not json\n",
                 '{"event":"","entityType":"user","entityId":"ghost"}\n',
                 '{"event":"x","entityId":"ghost","eventTime":"garbage"}\n']
    paths[1].write_text("".join(body)
                        + '{"event":"buy","entityType":"user","entityId":"torn')
    with open(paths[2], "a") as f:
        f.write('{"event":"buy","entityType":"user","entityId":"torn2"}')

    got = _scan(paths, n_threads, range_bytes)
    for name in ("event_codes", "entity_type_codes", "entity_ids",
                 "target_ids", "times_us"):
        assert np.array_equal(getattr(got, name), getattr(want, name)), name
        assert getattr(got, name).dtype == getattr(want, name).dtype
    assert np.array_equal(got.ratings, want.ratings, equal_nan=True)
    for name in ("event_dict", "entity_type_dict", "entity_dict",
                 "target_dict"):
        assert getattr(got, name).strings() == getattr(want, name).strings()
    assert got.entity_dict.strings()[-2:] == ["late-user-43", "late-user-44"]
    assert got.target_dict.strings()[-1] == "late-item-43"

    # property columns: one entry a (row, key) in row order, each value the
    # one the writer stored, each dictionary in first-appearance order
    keys, entries, strings = [], {}, {}
    for row, e in enumerate(events):
        for key, v in e.properties.items():
            if key not in entries:
                keys.append(key)
            entries.setdefault(key, []).append((row, _expected_value(v)))
            for s in (v if isinstance(v, list) else [v]):
                if isinstance(s, str) and s not in strings.setdefault(key, []):
                    strings[key].append(s)
    assert list(got.prop_columns) == keys
    assert {"rating", "price", "inStock", "category", "tags", "note",
            "nested"} == set(keys)
    for key, col in got.prop_columns.items():
        assert col.rows.tolist() == [row for row, _ in entries[key]]
        assert [col.value_at(j) for j in range(len(col))] == [
            v for _, v in entries[key]], key
        assert col.str_offs[0] == 0 and col.str_offs[-1] == len(col.codes)
        if key in ("category", "note"):
            assert col.dict.strings() == strings.get(key, [])
    assert got.prop_columns["tags"].dict.strings() == [
        "a", "b", "3", "2.5", "true"]
    assert set(got.prop_columns["rating"].kind.tolist()) == {0}
    assert got.prop_columns["inStock"].kind.tolist()[0] == 1
    assert set(got.prop_columns["note"].kind.tolist()) == {4}
    assert set(got.prop_columns["nested"].kind.tolist()) == {5}
