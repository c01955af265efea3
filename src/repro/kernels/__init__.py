"""Pallas TPU kernels (compiled on a TPU backend, interpreted elsewhere).

version_gather   — SI-V snapshot visibility gather (the paper's hot spot)
rss_gather       — RSS set-membership visibility gather (previous-version read)
rss_scan_agg     — fused RSS visibility resolve + on-device aggregate
                   (sum/count/count-below/min/max over member-visible pages)
flash_attention  — causal/SWA GQA prefill-train attention
decode_attention — one-token GQA decode over ring caches
wkv_scan         — RWKV6 data-dependent-decay recurrence

Every op's `interpret` argument defaults to None, resolved from the backend
at call time (`repro.kernels.config`): compiled on TPU, interpret mode on
CPU.  An explicit `interpret=` wins.
"""

from .config import default_interpret, resolve_interpret

__all__ = ["default_interpret", "resolve_interpret"]
