"""Decode-step roofline model: the analytic floor a decode dispatch
cannot beat, and the device tables to price it.

ROADMAP direction #2 ("Pallas paged decode attention kernel") starts
with "roofline first: extend tools/gpt_roofline.py with a decode-step
HBM model" — this module IS that model, shared between the engine's
perf attribution (snapshot()["perf"], /debug/perf), the roofline CLI
(tools/gpt_roofline.py --decode) and tests. A decode step is
memory-bound long before it is FLOP-bound: every step re-reads the
whole parameter set plus the K/V cache, so the HBM traffic term —
KV-read bytes per token as a function of batch, sequence length,
heads, and the attention path over the paged pool — is the yardstick
any paged attention kernel gets judged by.

Deliberately dependency-free (stdlib only): tools/perf_diff.py and
tools/gpt_roofline.py load this file directly via importlib without
importing the paddle_tpu package (no jax at tool startup), and the
engine imports it through paddle_tpu.observability.perf.

Layout model (why the gather costs more under plain XLA):

  * **paged_xla** (PagedKVPool behind a block table, composed in
    XLA): the gather MATERIALIZES a contiguous copy of every slot's
    full capacity before attention reads it — pool read + copy write
    + attention read, ~3x one direct read. That factor is exactly
    what the Pallas kernel deletes by reading blocks in place; it is
    what the engine builds only where
    ``ops.paged_attention.kernel_viable`` refuses the kernel (the
    CPU, shapes that do not tile);
  * **paged_pallas** (ops.paged_attention: the engine's choice
    wherever ``kernel_viable`` says yes): the Pallas kernel copies
    blocks into VMEM straight from the pool — gather factor 1.0, and
    no max-len over-read: it walks each slot's LIVE blocks only, so
    the read length is the live ``kv_len`` (callers may pass
    ``live_kv_len``), not the fixed cache capacity.
"""
import os

# published per-chip HBM bandwidth (bytes/sec) by PJRT device_kind
# prefix — the companion of the engine's _PEAK_FLOPS_BY_KIND table
_HBM_BPS_BY_KIND = (
    ("tpu v6", 1640e9),
    ("tpu v5p", 2765e9),
    ("tpu v5 lite", 819e9),
    ("tpu v5e", 819e9),
    ("tpu v4", 1228e9),
    ("tpu v3", 900e9),
    ("tpu v2", 700e9),
)

# XLA-composed paged attention: gather reads the pool, writes a
# contiguous copy, attention reads the copy back (vs one direct read
# by the in-place kernel)
PAGED_GATHER_FACTOR = 3.0

# the decode K/V layouts the model prices; per-layout gather
# materialization factor on the KV-read term
LAYOUTS = ("paged_xla", "paged_pallas")
_GATHER_FACTORS = {
    "paged_xla": PAGED_GATHER_FACTOR,
    "paged_pallas": 1.0,
}


def resolve_layout(layout):
    """``layout`` if the model prices it, else a ValueError naming
    the ones it does."""
    if layout not in _GATHER_FACTORS:
        raise ValueError(f"unknown KV layout {layout!r}; "
                         f"expected one of {LAYOUTS}")
    return layout


def peak_for(device_kind, table, env_var):
    """Look a PJRT device_kind up in a (prefix, peak) table; the env
    var covers kinds the table does not know. A TPU that neither
    names is an ERROR — it is never priced with another chip's peaks —
    and any other device (CPU) has no peak: None, and every fraction
    computed from it reports None."""
    kind = str(device_kind).lower()
    for prefix, peak in table:
        if kind.startswith(prefix):
            return peak
    env = os.environ.get(env_var)
    if env:
        return float(env)
    if kind.startswith("tpu"):
        raise ValueError(
            f"unknown TPU device_kind {device_kind!r}: add it to the "
            f"peak tables (serving/engine.py _PEAK_FLOPS_BY_KIND, "
            f"observability/perf/roofline.py _HBM_BPS_BY_KIND) or set "
            f"${env_var}")
    return None


def hbm_bps_for(device_kind):
    """HBM bandwidth (bytes/sec) for a PJRT device_kind (see
    ``peak_for``; $PADDLE_TPU_HBM_BPS covers unknown kinds)."""
    return peak_for(device_kind, _HBM_BPS_BY_KIND, "PADDLE_TPU_HBM_BPS")


def roofline_floor(flops, bytes_accessed, peak_flops, hbm_bps):
    """(floor_seconds, bound) — the time one dispatch cannot beat:
    max of the compute term and the memory term, with ``bound`` naming
    the binding resource ("flops" | "hbm"). Terms whose inputs are
    missing/zero drop out; (None, None) when nothing is computable."""
    t_flops = None
    if flops and peak_flops:
        t_flops = float(flops) / float(peak_flops)
    t_hbm = None
    if bytes_accessed and hbm_bps:
        t_hbm = float(bytes_accessed) / float(hbm_bps)
    if t_flops is None and t_hbm is None:
        return None, None
    if t_hbm is None or (t_flops is not None and t_flops >= t_hbm):
        return t_flops, "flops"
    return t_hbm, "hbm"


def kv_read_bytes_per_token(kv_len, num_layers, num_heads, head_dim,
                            kv_bytes=2, layout="paged_xla"):
    """HBM bytes attention reads to serve ONE decode token: K and V
    across every layer over ``kv_len`` positions, times the gather
    materialization factor on the XLA-composed paged layout (the
    Pallas in-place layout pays factor 1.0)."""
    base = 2.0 * num_layers * num_heads * head_dim * kv_len * kv_bytes
    return base * _GATHER_FACTORS[resolve_layout(layout)]


def decode_step_model(batch, kv_len, num_layers, num_heads, head_dim,
                      n_params, param_bytes=2, kv_bytes=2,
                      layout="paged_xla", live_kv_len=None,
                      peak_flops=None, hbm_bps=None):
    """Analytic cost of ONE pooled decode dispatch (``batch`` slots,
    one token each, attending over ``kv_len`` cached positions — the
    engine passes its fixed cache_len, since the fixed-shape program
    reads the whole pooled cache regardless of live lengths).

    On the ``paged_pallas`` layout the kernel stops reading at each
    slot's live length, so the KV-read term uses ``live_kv_len`` when
    given (the gather always reads the fixed ``kv_len`` — the
    over-read is part of its price).

    Returns a JSON-safe dict: the traffic decomposition (KV read per
    token and total, KV append write, parameter read), matmul +
    attention FLOPs, arithmetic intensity, and — when peak_flops /
    hbm_bps are given — the roofline floor and its binding resource.
    """
    layout = resolve_layout(layout)
    hidden = num_heads * head_dim
    kv_len_read = kv_len
    if layout == "paged_pallas" and live_kv_len is not None:
        kv_len_read = min(int(live_kv_len), int(kv_len))
    kv_tok = kv_read_bytes_per_token(kv_len_read, num_layers,
                                     num_heads, head_dim,
                                     kv_bytes=kv_bytes, layout=layout)
    kv_read = batch * kv_tok
    # one position appended per layer, K and V
    kv_write = batch * 2.0 * num_layers * num_heads * head_dim * kv_bytes
    param_read = float(n_params) * param_bytes
    bytes_total = kv_read + kv_write + param_read
    # dense matmuls touch every parameter twice per token; attention
    # is QK^T + AV, 2 * kv_len * hidden multiply-adds each, per layer
    flops = batch * (2.0 * n_params
                     + 4.0 * kv_len * hidden * num_layers)
    floor_s, bound = roofline_floor(flops, bytes_total, peak_flops,
                                    hbm_bps)
    return {
        "batch": int(batch),
        "kv_len": int(kv_len),
        "num_layers": int(num_layers),
        "num_heads": int(num_heads),
        "head_dim": int(head_dim),
        "n_params": int(n_params),
        # the attention path priced
        "layout": layout,
        "gather_factor": _GATHER_FACTORS[layout],
        "kv_len_read": int(kv_len_read),
        "kv_read_bytes_per_token": kv_tok,
        "kv_read_bytes": kv_read,
        "kv_write_bytes": kv_write,
        "param_read_bytes": param_read,
        "bytes_total": bytes_total,
        "flops": flops,
        "arithmetic_intensity": flops / bytes_total
        if bytes_total else None,
        "peak_flops": peak_flops,
        "hbm_bps": hbm_bps,
        "floor_s": floor_s,
        "floor_ms": round(floor_s * 1e3, 6)
        if floor_s is not None else None,
        "bound": bound,
    }
