"""ACIM performance-estimation model (paper Eqs. 2-11), vectorized in JAX.

Every public function accepts (h, w, l, b_adc) as scalars or equal-shaped
arrays and is `jit`/`vmap`-safe; the NSGA-II explorer evaluates whole
populations in one fused XLA call (the paper evaluates per-individual on a
Xeon — the vectorized evaluation is one of our TPU adaptations).

Model summary
-------------
SNR   (Eqs. 2-6): harmonic combination of input-quantization SQNR_i,
       analog noise SNR_a (cap mismatch + kT/C thermal + charge injection),
       and ADC quantization SQNR_y.  Dot-product length N = H/L.
SNR   (Eq. 11, simplified): 6*B - 10log10(H/L) - 10log10(k3/C0) + k4,
       with (k3, k4) fitted from the full model (`fit_eq11_constants`).
T     (Eq. 7): (H/L)*W / (t_com + t_set + t_conv); t_set = 0.69*tau*B,
       t_conv = t_conv_bit * B.  Reported as OPS = 2 * MACs.
E     (Eqs. 8-9): E_cc + E_ADC/(H/L) per 1b-MAC;
       E_ADC = k1*(B + log2 Vdd) + k2*4^B*Vdd^2  (Murmann [29]).
A     (Eq. 10): A_SRAM + A_LC/L + A_COMP/H + B*A_DFF/H   [F^2/bit].
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.constants import CAL28, CalibConstants

Array = jax.Array


# ----------------------------------------------------------------------
# SNR: full model, Eqs. 2-6
# ----------------------------------------------------------------------
def sqnr_input(n, cal: CalibConstants = CAL28):
    """SQNR_i = sigma_y0^2 / sigma_qi^2  (Eqs. 3-4), linear scale.

    For 1-bit signals the inputs are natively discrete, so input
    quantization noise vanishes; the paper's experiments are 1b x 1b and
    Eq. 11 carries no B_x/B_w term.  We keep the generic multi-bit form
    and return +inf when B_x == B_w == 1.
    """
    n = jnp.asarray(n, jnp.float32)
    if cal.b_w == 1 and cal.b_x == 1:
        return jnp.full_like(n, jnp.inf)
    delta_w = cal.w_m * 2.0 ** (-cal.b_w + 1)
    delta_x = cal.x_m * 2.0 ** (-cal.b_x)
    var_qi = (n / 12.0) * (delta_x**2 * cal.sigma_w**2 + delta_w**2 * cal.e_x2)
    var_y0 = n * cal.sigma_w**2 * cal.e_x2
    return var_y0 / var_qi


def snr_analog(n, cal: CalibConstants = CAL28):
    """SNR_a = sigma_y0^2 / sigma_eta^2  (Eq. 5), linear scale.

    sigma_eta^2 = (2/3)(1-4^-Bw) * N * (E[x^2] sigma_C0^2/C0^2
                                        + 2 sigma_theta^2 / Vdd^2
                                        + sigma_inj^2)
    with sigma_C0/C0 = kappa/sqrt(C0_fF) (metal-fringe mismatch [28]) and
    sigma_theta^2 = kT/C0.  N cancels against sigma_y0^2 = N sigma_w^2 E[x^2]:
    SNR_a is design-point independent for fixed C0 — which is exactly why
    Eq. 11 folds it into the constant -10log10(k3/C0) + k4 term.
    """
    n = jnp.asarray(n, jnp.float32)
    c0_f = cal.c0_ff * 1e-15
    mism_rel = (cal.kappa / np.sqrt(cal.c0_ff)) ** 2          # (sigma_C0/C0)^2
    therm_rel = 2.0 * (cal.kt / c0_f) / cal.v_dd**2           # 2 sigma_th^2/Vdd^2
    pref = (2.0 / 3.0) * (1.0 - 4.0 ** (-cal.b_w))
    var_eta_per_n = pref * (cal.e_x2 * mism_rel + therm_rel + cal.sigma_inj2)
    var_y0_per_n = cal.sigma_w**2 * cal.e_x2
    return jnp.broadcast_to(var_y0_per_n / var_eta_per_n, n.shape)


def sqnr_adc_db(n, b_adc, cal: CalibConstants = CAL28):
    """SQNR_y in dB (Eq. 6): 6*B_y + 4.8 - (zeta_x + zeta_w)_dB - 10log10(N)."""
    n = jnp.asarray(n, jnp.float32)
    b = jnp.asarray(b_adc, jnp.float32)
    return 6.0 * b + 4.8 - (cal.zeta_x_db + cal.zeta_w_db) - 10.0 * jnp.log10(n)


def snr_total_db(h, l, b_adc, cal: CalibConstants = CAL28):
    """SNR_T (Eq. 2): harmonic combination of SNR_pre and SQNR_y, in dB."""
    h = jnp.asarray(h, jnp.float32)
    l = jnp.asarray(l, jnp.float32)
    n = h / l
    inv_pre = 1.0 / snr_analog(n, cal) + 1.0 / sqnr_input(n, cal)
    sqnr_y = 10.0 ** (sqnr_adc_db(n, b_adc, cal) / 10.0)
    snr_t = 1.0 / (inv_pre + 1.0 / sqnr_y)
    return 10.0 * jnp.log10(snr_t)


# ----------------------------------------------------------------------
# SNR: simplified Eq. 11
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=8)
def fit_eq11_constants(cal: CalibConstants = CAL28) -> tuple[float, float]:
    """Fit (k3, k4) of Eq. 11 against the full model over the feasible space.

    Eq. 11: SNR_dB = 6*B - 10log10(H/L) - 10log10(k3/C0) + k4.
    We absorb the fit into the combined constant
        c = -10log10(k3/C0) + k4
    (only the combination is observable for fixed C0) and additionally
    report k3 derived analytically from Eq. 5 so that the C0 dependence is
    faithful:  k3 = pref * (E[x^2]*kappa^2 + 2*kT*1e15/Vdd^2) / (sw^2 E[x^2])
    in fF units, then k4 = c + 10log10(k3/C0).

    The fit is host numpy over concrete values, so it also answers from
    inside a `jit` trace (`evaluate_report` under `explorer.front_program`).
    """
    pref = (2.0 / 3.0) * (1.0 - 4.0 ** (-cal.b_w))
    k3 = pref * (cal.e_x2 * cal.kappa**2 + 2.0 * cal.kt * 1e15 / cal.v_dd**2) / (
        cal.sigma_w**2 * cal.e_x2)
    # least-squares for the additive constant c over the feasible grid
    pts = []
    for he in range(4, 13):
        for le in range(1, 6):
            for b in range(1, 9):
                if le <= he and (he - le) >= b:
                    pts.append((2**he, 2**le, b))
    hh = np.array([p[0] for p in pts], np.float32)
    ll = np.array([p[1] for p in pts], np.float32)
    bb = np.array([p[2] for p in pts], np.float32)
    with jax.ensure_compile_time_eval():
        full = np.asarray(snr_total_db(hh, ll, bb, cal))
    base = 6.0 * bb - 10.0 * np.log10(hh / ll)
    c = float(np.mean(full - base))
    k4 = c + 10.0 * float(np.log10(k3 / cal.c0_ff))
    return float(k3), float(k4)


def snr_simplified_db(h, l, b_adc, cal: CalibConstants = CAL28):
    """Eq. 11 with fitted (k3, k4)."""
    k3, k4 = fit_eq11_constants(cal)
    h = jnp.asarray(h, jnp.float32)
    l = jnp.asarray(l, jnp.float32)
    b = jnp.asarray(b_adc, jnp.float32)
    return 6.0 * b - 10.0 * jnp.log10(h / l) - 10.0 * np.log10(k3 / cal.c0_ff) + k4


# ----------------------------------------------------------------------
# Throughput, Eq. 7
# ----------------------------------------------------------------------
def cycle_time_s(b_adc, cal: CalibConstants = CAL28):
    b = jnp.asarray(b_adc, jnp.float32)
    t_set = 0.69 * cal.tau * b
    t_conv = cal.t_conv_bit * b
    return cal.t_com + t_set + t_conv


def throughput_ops(h, w, l, b_adc, cal: CalibConstants = CAL28):
    """Eq. 7 in OPS (1 MAC = 2 ops).  One conversion yields (H/L)*W MACs."""
    h = jnp.asarray(h, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    l = jnp.asarray(l, jnp.float32)
    macs_per_cycle = (h / l) * w
    return 2.0 * macs_per_cycle / cycle_time_s(b_adc, cal)


# ----------------------------------------------------------------------
# Energy, Eqs. 8-9
# ----------------------------------------------------------------------
def adc_energy_fj(b_adc, cal: CalibConstants = CAL28):
    """Eq. 9 (Murmann): E_ADC = k1*(B + log2 Vdd) + k2*4^B*Vdd^2, in fJ."""
    b = jnp.asarray(b_adc, jnp.float32)
    return cal.k1_fj * (b + jnp.log2(cal.v_dd)) + cal.k2_fj * 4.0**b * cal.v_dd**2


def energy_per_mac_fj(h, l, b_adc, cal: CalibConstants = CAL28):
    """Eq. 8: per-1b-MAC energy; the ADC is amortized over H/L MACs."""
    h = jnp.asarray(h, jnp.float32)
    l = jnp.asarray(l, jnp.float32)
    return cal.e_cc_fj + adc_energy_fj(b_adc, cal) / (h / l)


def energy_efficiency_tops_w(h, l, b_adc, cal: CalibConstants = CAL28):
    """TOPS/W = 2 ops / E_mac; with E in fJ this is 2000/E_fJ."""
    return 2000.0 / energy_per_mac_fj(h, l, b_adc, cal)


# ----------------------------------------------------------------------
# Area, Eq. 10
# ----------------------------------------------------------------------
def area_f2_per_bit(h, l, b_adc, cal: CalibConstants = CAL28):
    h = jnp.asarray(h, jnp.float32)
    l = jnp.asarray(l, jnp.float32)
    b = jnp.asarray(b_adc, jnp.float32)
    return cal.a_sram + cal.a_lc / l + cal.a_comp / h + b * cal.a_dff / h


# ----------------------------------------------------------------------
# Objective stack (Eq. 12): minimize [-f_SNR, -f_T, f_E, f_A]
# ----------------------------------------------------------------------
def objectives(h, w, l, b_adc, cal: CalibConstants = CAL28) -> Array:
    """Stack the four objectives, minimization orientation, shape (..., 4).

    Delegates to `objectives_from_operands` so the Eqs. 2-11 physics exists
    in exactly one place (the operand-traced form the explorers compile)."""
    return objectives_from_operands(h, w, l, b_adc, cal_operands(cal))


OBJECTIVE_NAMES = ("neg_snr_db", "neg_tops", "energy_fj_per_mac", "area_f2_per_bit")


# ----------------------------------------------------------------------
# Traced calibration operands (one-compile sweep support)
# ----------------------------------------------------------------------
class CalOperands(NamedTuple):
    """Calibration constants as traced f32 scalars.

    `objectives()` closes over a static `CalibConstants`, so every distinct
    calibration (and, upstream, every distinct array size) forces a retrace.
    `CalOperands` carries the same physics as *operand* arrays: the batched
    explorer vmaps one compiled program over a stack of these.  Design-point
    independent combinations (the pre-ADC inverse SNR, the ADC dB offset)
    are folded on the host so the traced math stays minimal.
    """

    inv_pre: Array        # 1/SNR_a + 1/SQNR_i (linear; N-independent, Eqs. 3-5)
    adc_off_db: Array     # 4.8 - zeta_x_dB - zeta_w_dB  (Eq. 6 constant)
    t_com: Array          # [s]
    t_set_per_b: Array    # 0.69 * tau [s/bit]
    t_conv_bit: Array     # [s/bit]
    e_cc_fj: Array        # E_compute + E_control [fJ]
    k1_fj: Array
    k2_fj: Array
    log2_vdd: Array
    vdd2: Array
    a_sram: Array
    a_lc: Array
    a_comp: Array
    a_dff: Array


def cal_operands(cal: CalibConstants = CAL28) -> CalOperands:
    """Fold a static `CalibConstants` into traced scalar operands."""
    n_probe = jnp.float32(1.0)  # SNR_a and SQNR_i are N-independent (see Eq. 5)
    inv_pre = 1.0 / snr_analog(n_probe, cal) + 1.0 / sqnr_input(n_probe, cal)
    f32 = lambda v: jnp.float32(v)  # noqa: E731
    return CalOperands(
        inv_pre=jnp.reshape(inv_pre, ()).astype(jnp.float32),
        adc_off_db=f32(4.8 - cal.zeta_x_db - cal.zeta_w_db),
        t_com=f32(cal.t_com),
        t_set_per_b=f32(0.69 * cal.tau),
        t_conv_bit=f32(cal.t_conv_bit),
        e_cc_fj=f32(cal.e_cc_fj),
        k1_fj=f32(cal.k1_fj),
        k2_fj=f32(cal.k2_fj),
        log2_vdd=f32(np.log2(cal.v_dd)),
        vdd2=f32(cal.v_dd**2),
        a_sram=f32(cal.a_sram),
        a_lc=f32(cal.a_lc),
        a_comp=f32(cal.a_comp),
        a_dff=f32(cal.a_dff),
    )


def objectives_from_operands(h, w, l, b_adc, ops: CalOperands) -> Array:
    """Eq. 12 objective stack with *traced* calibration operands.

    Same model as `objectives()` (Eqs. 2-11) but every constant is an
    operand, so one compiled program serves any (array size, calibration)
    batch.  Shapes broadcast: scalar operands with (...,) design points, or
    leading batch dims on both under `vmap`.
    """
    h = jnp.asarray(h, jnp.float32)
    w = jnp.asarray(w, jnp.float32)
    l = jnp.asarray(l, jnp.float32)
    b = jnp.asarray(b_adc, jnp.float32)
    n = h / l
    # SNR_T (Eqs. 2-6): pre-ADC inverse SNR is a folded constant.
    sqnr_y_db = 6.0 * b + ops.adc_off_db - 10.0 * jnp.log10(n)
    sqnr_y = 10.0 ** (sqnr_y_db / 10.0)
    snr_db = 10.0 * jnp.log10(1.0 / (ops.inv_pre + 1.0 / sqnr_y))
    # Throughput (Eq. 7), TOPS.
    t_cycle = ops.t_com + ops.t_set_per_b * b + ops.t_conv_bit * b
    tops = 2.0 * n * w / t_cycle / 1e12
    # Energy (Eqs. 8-9), fJ per 1b MAC.
    e_adc = ops.k1_fj * (b + ops.log2_vdd) + ops.k2_fj * 4.0**b * ops.vdd2
    e = ops.e_cc_fj + e_adc / n
    # Area (Eq. 10), F^2/bit.
    a = ops.a_sram + ops.a_lc / l + ops.a_comp / h + b * ops.a_dff / h
    return jnp.stack([-snr_db, -tops, e, a], axis=-1)


REPORT_METRICS = ("snr_db", "snr_eq11_db", "tops", "energy_fj_per_mac",
                  "tops_per_w", "area_f2_per_bit", "cycle_ns")


def evaluate_report(h, w, l, b_adc, cal: CalibConstants = CAL28) -> dict:
    """Human-oriented metrics for one or more design points, keyed in
    `REPORT_METRICS` order.  Traceable: `cal` is static."""
    return {
        "snr_db": snr_total_db(h, l, b_adc, cal),
        "snr_eq11_db": snr_simplified_db(h, l, b_adc, cal),
        "tops": throughput_ops(h, w, l, b_adc, cal) / 1e12,
        "energy_fj_per_mac": energy_per_mac_fj(h, l, b_adc, cal),
        "tops_per_w": energy_efficiency_tops_w(h, l, b_adc, cal),
        "area_f2_per_bit": area_f2_per_bit(h, l, b_adc, cal),
        "cycle_ns": cycle_time_s(b_adc, cal) * 1e9,
    }
