"""The least time of the traced batch's decode steps over their device time,
in %. A step's least time is the larger of its operations at the bf16 peak
and its bytes at full HBM bandwidth: every weight once, the held experts
at the share a batch's tokens reach, the live latent prefix read and the
new entry written (benchmarks/chip/flops_mla_moe.py). Steps are weighted
by the mean over the batch's positions, as in mfu.decode. A model without
latent attention and experts is counted by benchmarks/chip/flops.py, as in
the reader without the suffix."""
from benchmarks.chip import flops, flops_mla_moe


def read(r):
    call = r["summary"]["calls"].get("jit_decode_step")
    if not call or not call["count"]:
        return None
    s, peaks = r["shape"], r["peaks"]
    count = flops_mla_moe if flops_mla_moe.applies(r["model"]) else flops
    positions = range(s["prompt_len"], s["prompt_len"] + s["new_tokens"] - 1)
    mean = sum(count.decode_least_s(
        r["model"], r["state_bytes"], s["batch"], p,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
        for p in positions) / len(positions)
    return 100.0 * mean * call["count"] / call["device_s"]
