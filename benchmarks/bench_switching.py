"""Paper Figure 4 + Observation 1 + Figure 9: switching cost.

(a) Theoretical checkpoint-transfer time (Gandiva-style suspend/resume
    moving persistent memory over PCIe at 30 GB/s, the paper's number) vs
    model inference latency — the motivation for keep-resident switching;
(b) Salus's measured live switch latency on the host (keep-resident: zero
    bytes moved): from one job's wait on the device to the return of the
    next one's dispatch, from the executor's span log."""
from __future__ import annotations

import jax

from benchmarks.common import emit
from repro.configs import get_config
from repro.core import GB, MB, MemoryProfile, SalusExecutor, VirtualDevice, get_policy
from repro.core.profiles import PAPER_WORKLOADS

TRANSFER_BPS = 30e9  # paper's Fig. 4 transfer speed


def run():
    # (a) transfer-vs-latency for the paper workloads
    worst = 0.0
    for name, (p_mb, e_mb, iter_s, util) in sorted(PAPER_WORKLOADS.items()):
        transfer_s = 2 * p_mb * 2**20 / TRANSFER_BPS  # out + back in
        infer_s = iter_s / 3.0
        worst = max(worst, transfer_s / infer_s)
    emit(
        "fig4_transfer_vs_inference",
        0.0,
        f"worst_transfer_over_latency={worst:.1f}x;paper=several_x -> keep-resident wins",
    )

    # (b) live Salus switch latency between two real jobs sharing a lane
    from benchmarks.bench_overhead import build_session_parts

    # capacity sized so the two jobs must time-share ONE lane
    ex = SalusExecutor(capacity=1 * GB, policy=get_policy("fair"))
    vdev = VirtualDevice(ex)
    prof = MemoryProfile(64 * MB, 700 * MB)
    for i in range(2):
        step, state, data_fn = build_session_parts("gemma-2b", seed=i)
        vdev.create_session(f"g{i}", step, state, data_fn, n_iters=8, profile=prof)
    rep = vdev.run()
    lat = sorted(rep.switch_latencies)
    med = lat[len(lat) // 2] if lat else 0.0
    emit(
        "fig9_salus_switch_latency",
        med * 1e6,
        f"n_switches={len(lat)};median_us={med*1e6:.1f};bytes_moved=0 (persistent stays resident)",
    )


if __name__ == "__main__":
    run()
