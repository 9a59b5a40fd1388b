"""Plain reference of the layout flow: template placement, the macro's
top-level nets, sequential maze routing and DRC, for one design point.

Follows the flow the paper describes (Sec. 3.3, Fig. 7) and the cell
library the configuration states, in numpy on the host, one spec at a
time: no batching, no device program and nothing imported from the
system under test.  Routing is a breadth-first wavefront per net, nets
longest first, on a coarse grid with a per-cell track capacity; a
path walks back from each target to the hub, at every step to the first
neighbour one step nearer in the order down, up, right, left.  A pin
on a full cell may still be entered, at one step more than its best
free neighbour.

`row(spec, cfg)` gives the layout row the service must report for the
spec: area, routed and failed nets, wirelength, DRC.
"""
from __future__ import annotations

import itertools
import math

import numpy as np

INF = np.iinfo(np.int32).max
NEIGHBORS = ((1, 0), (-1, 0), (0, 1), (0, -1))
MAX_ROW_DRIVERS = 64
# name: (area source, aspect ratio); areas in F^2
CELLS = {"SRAM8T": ("a_sram", 1.3), "CAPLC": ("a_lc", 1.0),
         "COMP": ("a_comp*0.25", 2.0), "SARLOGIC": ("a_comp*0.75", 3.0),
         "DFF": ("a_dff", 1.5), "RBLSW": ("a_dff*0.2", 1.0),
         "ROWDRV": ("420", 0.5)}
PERIPHERY = ("RBLSW", "COMP", "SARLOGIC", "DFF")


def _area(src: str, cal: dict) -> float:
    if "*" in src:
        key, factor = src.split("*")
        return cal[key] * float(factor)
    return cal[src] if src in cal else float(src)


def library(cal: dict) -> dict:
    """(width, height) of each template cell: the calibrated area at the
    cell's aspect ratio, on the F grid."""
    out = {}
    for name, (src, aspect) in CELLS.items():
        a = _area(src, cal)
        w = max(2, int(round(math.sqrt(a * aspect))))
        out[name] = (w, max(2, int(round(a / w))))
    return out


def geometry(cal: dict) -> dict:
    lib = library(cal)
    col_w = lib["SRAM8T"][0] + lib["CAPLC"][0]
    pitch = {k: max(1, -(-lib[k][0] * lib[k][1] // col_w)) for k in PERIPHERY}
    # periphery order under the column: least half-perimeter wirelength
    # of RBL (to switches and comparator), comparator->SAR, SAR->DFF
    best = None
    for order in itertools.permutations(PERIPHERY):
        y, pos = 0, {}
        for k in order:
            pos[k] = y
            y += lib[k][1]
        cost = (pos["RBLSW"] + lib["RBLSW"][1] + pos["COMP"] + lib["COMP"][1]
                + abs(pos["COMP"] - pos["SARLOGIC"])
                + abs(pos["SARLOGIC"] - pos["DFF"]))
        if best is None or cost < best[0]:
            best = (cost, order)
    return {"lib": lib, "col_w": col_w, "pitch": pitch, "order": best[1],
            "xshift": lib["ROWDRV"][0] + 2}


def _center(x, y, w, h):
    return (x + w // 2, y + h // 2)


def place(spec, g: dict) -> dict:
    """Macro extent, the pins of the top-level nets, and the rectangles
    of column 0 (every column is an x-translate of it)."""
    h, w, l, b = spec
    lib = g["lib"]
    s_w, s_h = lib["SRAM8T"]
    c_w, c_h = lib["CAPLC"]
    d_w, d_h = lib["ROWDRV"]
    n_la = h // l
    n_sw = b + (1 if n_la > (1 << b) else 0)   # CDAC groups 1:1:2:..:2^(B-1)
    la_h = max(l * s_h, c_h)
    array_h = n_la * la_h
    counts = {"RBLSW": n_sw, "COMP": 1, "SARLOGIC": 1, "DFF": b}
    y, py = 0, {}
    for k in g["order"]:
        py[k] = y
        y += counts[k] * g["pitch"][k] + 1
    width = w * g["col_w"] + d_w + 2
    height = array_h + y
    cap_y = (la_h - c_h) // 2
    drv_pitch = max(la_h // max(l, 1), d_h)
    col_w, p = g["col_w"], g["pitch"]

    def col_x(j):
        return g["xshift"] + j * col_w

    def sram(j, r):
        return (col_x(j), (r // l) * la_h + (r % l) * s_h, s_w, s_h)

    def cap(j, i):
        return (col_x(j) + s_w, i * la_h + cap_y, c_w, c_h)

    def comp(j):
        return (col_x(j), array_h + py["COMP"], col_w, p["COMP"])

    def sar(j):
        return (col_x(j), array_h + py["SARLOGIC"], col_w, p["SARLOGIC"])

    nets = []
    for j in range(w):
        nets.append([_center(*comp(j)), _center(*cap(j, 0)),
                     _center(*cap(j, n_la - 1))])
        nets.append([_center(*comp(j)), _center(*sar(j))])
    for r in range(min(h, MAX_ROW_DRIVERS)):
        nets.append([_center(0, r * drv_pitch, d_w, d_h),
                     _center(*sram(w - 1, r))])
    col0 = ([sram(0, r) for r in range(h)] + [cap(0, i) for i in range(n_la)]
            + [(col_x(0), array_h + py["RBLSW"] + i * p["RBLSW"], col_w,
                p["RBLSW"]) for i in range(n_sw)]
            + [comp(0), sar(0)]
            + [(col_x(0), array_h + py["DFF"] + i * p["DFF"], col_w, p["DFF"])
               for i in range(b)])
    rd = [(0, r * drv_pitch, d_w, d_h) for r in range(min(h, MAX_ROW_DRIVERS))]
    return {"width": width, "height": height, "nets": nets,
            "col0": np.array(col0, np.int64), "rd": np.array(rd, np.int64)}


def drc_clean(pl: dict, w: int, col_w: int) -> bool:
    """No two rectangles of a column overlap; nothing lies outside the
    macro (one F of slack on the far edges)."""
    r = pl["col0"]
    n = len(r)
    overlaps = 0
    for i in range(0, n, 512):
        a = r[i:i + 512, None, :]
        bb = r[None, :, :]
        ov = ((a[..., 0] < bb[..., 0] + bb[..., 2])
              & (bb[..., 0] < a[..., 0] + a[..., 2])
              & (a[..., 1] < bb[..., 1] + bb[..., 3])
              & (bb[..., 1] < a[..., 1] + a[..., 3]))
        upper = np.arange(i, i + len(a))[:, None] < np.arange(n)[None, :]
        overlaps += int(np.sum(ov & upper))
    last = r + np.array([(w - 1) * col_w, 0, 0, 0])
    rects = np.concatenate([r, last, pl["rd"]])
    oob = int(np.sum((rects[:, 1] + rects[:, 3] > pl["height"] + 1)
                     | (rects[:, 0] + rects[:, 2] > pl["width"] + 1)))
    return overlaps == 0 and oob == 0


def _bfs(blocked: np.ndarray, stride: int, src: int, targets) -> np.ndarray:
    """Breadth-first distances from `src` over a bordered flat grid,
    stopped once every target is settled (a free target reached, or a
    full one with a reached neighbour)."""
    dist = np.full(blocked.size, INF, np.int64)
    dist[src] = 0
    offs = np.array([stride, -stride, 1, -1])
    front = np.array([src])
    d = 0

    def settled():
        for t in targets:
            if dist[t] != INF:
                continue
            if blocked[t] and np.any(dist[t + offs] != INF):
                continue
            return False
        return True

    while front.size and not settled():
        nb = (front[:, None] + offs[None, :]).ravel()
        nb = nb[~blocked[nb]]
        nb = np.unique(nb[dist[nb] == INF])
        d += 1
        dist[nb] = d
        front = nb
    return dist


def _path(dist: np.ndarray, stride: int, t: int):
    """Cells from the hub to target `t` (inclusive), or None."""
    offs = [dy * stride + dx for dy, dx in NEIGHBORS]
    d = dist[t]
    if d == INF:
        near = min(dist[t + o] for o in offs)
        if near == INF:
            return None
        d = near + 1
    path = [t]
    cur = t
    while d > 0:
        for o in offs:
            if dist[cur + o] == d - 1:
                cur += o
                break
        else:
            return None
        path.append(cur)
        d -= 1
    return path


def route(pl: dict, coarse: int, capacity: int) -> tuple[int, int, int]:
    """Sequential routing of every net; returns (routed, failed,
    wirelength in grid cells)."""
    gh = max(2, pl["height"] // coarse + 3)
    gw = max(2, pl["width"] // coarse + 2)
    stride = gw + 2
    occ = np.zeros((gh + 2) * stride, np.int64)
    border = np.ones((gh + 2, stride), bool)
    border[1:-1, 1:-1] = False
    border = border.ravel()

    def cell(p):
        gy = min(gh - 1, max(0, p[1] // coarse))
        gx = min(gw - 1, max(0, p[0] // coarse))
        return (gy + 1) * stride + gx + 1

    def span(pins):
        xs = [q[0] for q in pins]
        ys = [q[1] for q in pins]
        return (max(xs) - min(xs)) + (max(ys) - min(ys))

    routed = failed = wl = 0
    for pins in sorted(pl["nets"], key=lambda n: -span(n)):
        hub = cell(pins[0])
        tgts = [cell(q) for q in pins[1:]]
        blocked = border | (occ >= capacity)
        dist = _bfs(blocked, stride, hub, tgts)
        paths = [_path(dist, stride, t) for t in tgts]
        if any(p is None for p in paths):
            failed += 1
            continue
        for p in paths:
            np.add.at(occ, p, 1)
            wl += len(p)
        routed += 1
    return routed, failed, wl


def row(spec, cfg: dict) -> dict:
    """The layout row of one design point (h, w, l, b)."""
    cal = cfg["cal"]
    g = geometry(cal)
    pl = place(spec, g)
    h, w, l, b = spec
    routed, failed, wl = route(pl, cfg["request"]["coarse"],
                               cfg["request"]["capacity"])
    area = pl["width"] * pl["height"] / (h * w)
    est = (cal["a_sram"] + cal["a_lc"] / l + cal["a_comp"] / h
           + b * cal["a_dff"] / h)
    return {"h": h, "w": w, "l": l, "b_adc": b,
            "layout_area_f2_per_bit": area,
            "estimator_area_f2_per_bit": est,
            "area_model_error": area / est - 1.0,
            "routed_nets": routed, "failed_nets": failed,
            "route_success": routed / (routed + failed),
            "wirelength": wl, "drc_clean": drc_clean(pl, w, g["col_w"])}
