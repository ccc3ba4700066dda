"""Linear Road's geometry, checked apart from the served path: every
generated report lies in exactly one zone by the literal even-odd rule
over every zone, the reference's zone of each report is Linear Road's
own segment, floor(position / 5280 ft), of the report's expressway and
direction, and the traffic keeps the source's rhythm (a report per
vehicle every 30 s) and its share of segment crossings."""

import numpy as np
import pytest

from benchmark import loader, traffic, world

SEED = 2 ** 31 + 27
BASE_MS = 1_700_000_000_000


@pytest.fixture(scope="module")
def road():
    cfg = world.load_config("linear_road")
    dep = loader.deployment(cfg)
    n = dep.n_devices(cfg)
    tokens = [f"veh-{i}" for i in range(n)]
    w = world.World(cfg=cfg, tokens=np.array(tokens, dtype=object),
                    token_index={t: i for i, t in enumerate(tokens)},
                    device_idx=np.arange(1, n + 1, dtype=np.int64))
    mix = traffic.load_mix("crossings")
    n_rec = traffic.pool_records(mix, dep.events_per_record(mix))
    return dep, cfg, w, dep.make_traffic(w, mix, SEED, n_rec, BASE_MS)


def _even_odd(lat, lon, polygon):
    lat = np.asarray(lat, np.float32)
    lon = np.asarray(lon, np.float32)
    odd = np.zeros(lat.shape, bool)
    vertices = np.asarray(polygon, np.float32)
    for (y1, x1), (y2, x2) in zip(vertices, np.roll(vertices, -1, 0)):
        if y1 == y2:
            continue
        crossing = x1 + (x2 - x1) * (lat - y1) / (y2 - y1)
        odd ^= ((y1 > lat) != (y2 > lat)) & (lon < crossing)
    return odd


def test_every_report_lies_in_exactly_one_zone(road):
    dep, cfg, _, tr = road
    pick = np.random.default_rng(1).choice(tr.n, 2000, replace=False)
    lat, lon = tr.lat[pick], tr.lon[pick]
    holding = np.zeros(pick.size, np.int64)
    found = np.full(pick.size, -1, np.int64)
    for z, (_, rect, _) in enumerate(dep.zones(cfg)):
        inside = _even_odd(lat, lon, rect)
        holding += inside
        found[inside] = z
    assert (holding == 1).all()
    assert (found == dep.zone_of(cfg, lat, lon)).all()


def test_reference_zone_is_the_segment_of_the_position(road):
    dep, cfg, w, tr = road
    zone = dep.zone_of(cfg, tr.lat, tr.lon)
    segments, directions = int(cfg["segments"]), int(cfg["directions"])
    assert (tr.position >= 0).all() and (tr.position < segments * 5280).all()
    assert (zone % segments == tr.position // 5280).all()
    # the band is the vehicle's own: the same for every report of it
    band = zone // segments
    first = np.full(w.n, -1, np.int64)
    first[tr.record_dev[::-1]] = band[::-1]
    assert (band == first[tr.record_dev]).all()
    assert set(np.unique(band % directions).tolist()) == {0, 1}


def test_reports_every_30_s_and_the_share_of_crossings(road):
    dep, cfg, w, tr = road
    order = np.lexsort((tr.ts, tr.record_dev))
    same = tr.record_dev[order][1:] == tr.record_dev[order][:-1]
    gaps = np.diff(tr.ts[order])[same]
    assert (gaps == int(cfg["report_interval_ms"])).all()
    # every vehicle reports once in the warm-up round
    warm = traffic.load_mix("crossings")["warmup_records"]
    assert np.unique(tr.record_dev[:warm]).size == w.n == warm
    assert ((tr.speed >= 0) & (tr.speed <= 100)).all()
    assert (tr.speed == np.rint(tr.speed)).all()
    # past the first round about 46 % of the reports enter a segment
    alerts = dep.expected(w, tr).alerts
    steady = (alerts[:, 0] >= tr.ts[warm]).sum() / (tr.n - warm)
    assert 0.40 < steady < 0.52, steady
