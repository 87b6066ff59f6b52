"""The operations of the traced batch's decode steps through latent attention
(absorbed, over the live latents), the dense layers and the held experts
(benchmarks/chip/flops_mla_moe.py), over their device time and the chip's
bf16 peak, in %. Steps are weighted by the mean over the batch's
positions, as in mfu.decode. A model without latent attention and experts
is counted by benchmarks/chip/flops.py, as in the reader without the
suffix."""
from benchmarks.chip import flops, flops_mla_moe


def read(r):
    call = r["summary"]["calls"].get("jit_decode_step")
    if not call or not call["count"]:
        return None
    s = r["shape"]
    count = flops_mla_moe if flops_mla_moe.applies(r["model"]) else flops
    positions = range(s["prompt_len"], s["prompt_len"] + s["new_tokens"] - 1)
    mean = sum(count.decode_flops(r["model"], s["batch"], p)
               for p in positions) / len(positions)
    return 100.0 * mean * call["count"] / (
        call["device_s"] * r["peaks"]["bf16_flops_per_s"])
