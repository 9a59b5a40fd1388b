"""Plain float64 reference of the EasyACIM estimation model (paper Eqs. 2-11).

Written from the equations, in numpy and float64, with the calibration
constants that the configuration file states.  It imports nothing of the
system under test.  `dtype` lets the control of the benchmark evaluate
the same equations in a lower precision (bfloat16 via `ml_dtypes`).

Every function takes (h, w, l, b) as equal-shaped arrays of one design
point each: H rows, W columns, L cells per local array, B ADC bits.
"""
from __future__ import annotations

import math

import numpy as np

BOLTZMANN = 1.380649e-23  # J/K

METRICS = ("snr_db", "snr_eq11_db", "tops", "energy_fj_per_mac",
           "tops_per_w", "area_f2_per_bit", "cycle_ns")


def _inv_pre(cal: dict) -> float:
    """1/SNR_a + 1/SQNR_i (Eqs. 3-5); independent of the design point."""
    kt = BOLTZMANN * cal["temperature_k"]
    mism = (cal["kappa"] / math.sqrt(cal["c0_ff"])) ** 2
    therm = 2.0 * (kt / (cal["c0_ff"] * 1e-15)) / cal["v_dd"] ** 2
    pref = (2.0 / 3.0) * (1.0 - 4.0 ** (-cal["b_w"]))
    var_eta = pref * (cal["e_x2"] * mism + therm + cal["sigma_inj2"])
    snr_a = cal["sigma_w"] ** 2 * cal["e_x2"] / var_eta
    if cal["b_w"] == 1 and cal["b_x"] == 1:
        inv_sqnr_i = 0.0        # 1-bit signals carry no quantization noise
    else:
        dw = cal["w_m"] * 2.0 ** (-cal["b_w"] + 1)
        dx = cal["x_m"] * 2.0 ** (-cal["b_x"])
        var_qi = (dx ** 2 * cal["sigma_w"] ** 2 + dw ** 2 * cal["e_x2"]) / 12.0
        inv_sqnr_i = var_qi / (cal["sigma_w"] ** 2 * cal["e_x2"])
    return 1.0 / snr_a + inv_sqnr_i


def _snr_db(h, l, b, cal, dt):
    n = h / l
    zeta = (20.0 * math.log10(cal["x_m"] / cal["sigma_x"])
            + 20.0 * math.log10(cal["w_m"] / cal["sigma_w"]))
    sqnr_y_db = dt(6.0) * b + dt(4.8 - zeta) - dt(10.0) * np.log10(n)
    inv_y = dt(10.0) ** (-sqnr_y_db / dt(10.0))
    return dt(10.0) * np.log10(dt(1.0) / (dt(_inv_pre(cal)) + inv_y))


def _eq11_offset(cal: dict) -> float:
    """Eq. 11's additive constant, least squares over the feasible grid
    (H 16..4096, L 2..32, B 1..8 with H/L >= 2^B), as the paper fits it."""
    pts = [(2.0 ** he, 2.0 ** le, float(b))
           for he in range(4, 13) for le in range(1, 6) for b in range(1, 9)
           if le <= he and he - le >= b]
    h, l, b = (np.array(c) for c in zip(*pts))
    full = _snr_db(h, l, b, cal, np.float64)
    return float(np.mean(full - (6.0 * b - 10.0 * np.log10(h / l))))


def report(h, w, l, b, cal: dict, dtype=np.float64) -> dict:
    """The metric columns of a served front, for each design point."""
    dt = np.dtype(dtype).type
    h, w, l, b = (np.asarray(v).astype(dtype) for v in (h, w, l, b))
    n = h / l
    t_cycle = (dt(cal["t_com"]) + dt(0.69 * cal["tau"]) * b
               + dt(cal["t_conv_bit"]) * b)
    e_adc = (dt(cal["k1_fj"]) * (b + dt(math.log2(cal["v_dd"])))
             + dt(cal["k2_fj"]) * dt(4.0) ** b * dt(cal["v_dd"] ** 2))
    energy = dt(cal["e_compute_fj"] + cal["e_control_fj"]) + e_adc / n
    return {
        "snr_db": _snr_db(h, l, b, cal, dt),
        "snr_eq11_db": (dt(6.0) * b - dt(10.0) * np.log10(n)
                        + dt(_eq11_offset(cal))),
        "tops": dt(2.0) * n * w / t_cycle / dt(1e12),
        "energy_fj_per_mac": energy,
        "tops_per_w": dt(2000.0) / energy,
        "area_f2_per_bit": (dt(cal["a_sram"]) + dt(cal["a_lc"]) / l
                            + dt(cal["a_comp"]) / h
                            + b * dt(cal["a_dff"]) / h),
        "cycle_ns": t_cycle * dt(1e9),
    }


def objectives(rep: dict) -> np.ndarray:
    """Eq. 12's minimisation stack [-SNR, -TOPS, energy, area], (P, 4)."""
    return np.stack([-np.asarray(rep["snr_db"], np.float64),
                     -np.asarray(rep["tops"], np.float64),
                     np.asarray(rep["energy_fj_per_mac"], np.float64),
                     np.asarray(rep["area_f2_per_bit"], np.float64)], axis=1)


def dominated(objs: np.ndarray) -> np.ndarray:
    """(P,) True where another point is no worse everywhere and better
    somewhere (minimisation)."""
    a = objs[:, None, :]
    b = objs[None, :, :]
    dom = np.all(b <= a, axis=2) & np.any(b < a, axis=2)   # [i, j]: j dom i
    return dom.any(axis=1)


def feasible(size: int, cal: dict) -> list[tuple]:
    """Every design point (h, w, l, b) of an array of `size` bits: H and L
    powers of two within the calibration's bounds, W = size / H at least
    `w_min`, B within its bounds, H >= L and H / L >= 2^B (Eq. 12)."""
    out = []
    for he in range(int(math.log2(cal["h_min"])),
                    int(math.log2(cal["h_max"])) + 1):
        h = 2 ** he
        if size // h < cal["w_min"]:
            continue
        for le in range(int(math.log2(cal["l_min"])),
                        int(math.log2(cal["l_max"])) + 1):
            l = 2 ** le
            for b in range(cal["b_min"], cal["b_max"] + 1):
                if l <= h and h // l >= 2 ** b:
                    out.append((h, size // h, l, b))
    return out


def pareto_set(size: int, cal: dict) -> set:
    """The exact Pareto set of Eq. 12 over the whole feasible space, in
    float64: the front a complete exploration serves."""
    pts = feasible(size, cal)
    rep = report(*(np.array(c, np.float64) for c in zip(*pts)), cal)
    return {p for p, d in zip(pts, dominated(objectives(rep))) if not d}


def keep(rep: dict, req: dict) -> np.ndarray:
    """The distillation filter (paper Fig. 4, 'remove undesired solutions')
    against the request's requirements."""
    return ((rep["snr_db"] >= req.get("min_snr_db", -np.inf))
            & (rep["tops"] >= req.get("min_tops", 0.0))
            & (rep["energy_fj_per_mac"] <= req.get("max_energy_fj", np.inf))
            & (rep["area_f2_per_bit"] <= req.get("max_area", np.inf))
            & (rep["tops_per_w"] >= req.get("min_tops_per_w", 0.0)))


def near_threshold(rep: dict, req: dict, rel: float = 1e-5) -> np.ndarray:
    """Points whose reference value lies within `rel` of a threshold: a
    float32 program may fairly put them on either side."""
    near = np.zeros(len(rep["tops"]), bool)
    for metric, key in (("snr_db", "min_snr_db"), ("tops", "min_tops"),
                        ("energy_fj_per_mac", "max_energy_fj"),
                        ("area_f2_per_bit", "max_area"),
                        ("tops_per_w", "min_tops_per_w")):
        t = req.get(key)
        if t is None or not np.isfinite(t):
            continue
        v = np.asarray(rep[metric], np.float64)
        near |= np.abs(v - t) <= rel * max(abs(t), 1.0)
    return near
