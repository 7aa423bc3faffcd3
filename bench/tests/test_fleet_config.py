"""The `pods1024_ls4` configuration and its `ls4_steady` mix, as the
harness finds them by name, and the load the mix offers the pods'
leaf links."""
import numpy as np

from bench import fleet, gen, spec

CELL = "pods1024_ls4.steady"


def test_the_cell_loads_by_name():
    cell = spec.Cell(CELL)
    cfg = cell.config
    assert cell.chips == 4
    assert cfg["tenants"] == 1024 and cfg["tenants"] % cell.chips == 0
    assert cfg["num_ports"] == 24
    assert cell.traffic == {"load": 0.27}
    assert fleet.topology(cfg).wc_fill == "maxmin"
    assert cfg["reduced"] == []
    # the same scheduler and coflow shapes as fb150
    fb = spec.Cell("fb150.steady").config
    assert cfg["params"] == fb["params"]
    assert cfg["coflows"] == fb["coflows"]
    names = {m["name"] for m in cell.per_layer}
    assert {"maxmin_roofline", "tick.maxmin_ms",
            "pool.scatter_rows"} <= names
    # the greedy fill's metrics: that fill does not run in this cell
    for m in ("tick.wc_fill_ms", "tick.wc_ns_per_trip", "tick.wc_fills"):
        assert m not in names


def test_uplinks_are_offered_about_nine_tenths():
    """Over each tenant's rate sample (its first 526 coflows), the bytes
    that leave a leaf, over the uplinks' capacity (hosts_per_leaf ports
    over the oversubscription: one port's bandwidth at 4:1), averaged
    over 64 tenants and their leaves."""
    cell = spec.Cell(CELL)
    cfg = dict(cell.config, tenants=64)
    topo = cfg["topology"]
    h = topo["hosts_per_leaf"]
    leaves = cfg["num_ports"] // h
    cap = h * cfg["params"]["port_bw"] / topo["oversub"]
    up, down = [], []
    for st in gen.streams(cfg, cell.traffic, 2**31 + 11):
        horizon = gen.RATE_COFLOWS / st.rate
        u, d = np.zeros(leaves), np.zeros(leaves)
        for s in st.until(horizon):
            a, b = s.src // h, s.dst // h
            out = a != b
            np.add.at(u, a[out], s.size[out])
            np.add.at(d, b[out], s.size[out])
        up.append(u / (cap * horizon))
        down.append(d / (cap * horizon))
    assert 0.85 <= np.mean(up) <= 0.95
    assert 0.85 <= np.mean(down) <= 0.95
