"""The operations a batch's prompt needs through latent attention, the dense
layers and the held experts (benchmarks/chip/flops_mla_moe.py) over the
traced prefill's device time and the chip's bf16 peak, in %. A model
without latent attention and experts is counted by
benchmarks/chip/flops.py, as in the reader without the suffix."""
from benchmarks.chip import flops, flops_mla_moe


def read(r):
    call = r["summary"]["calls"].get("jit_prefill")
    if not call or not call["count"]:
        return None
    s = r["shape"]
    count = flops_mla_moe if flops_mla_moe.applies(r["model"]) else flops
    need = count.prefill_flops(r["model"], s["batch"], s["prompt_len"])
    per_call = call["device_s"] / call["count"]
    return 100.0 * need / (per_call * r["peaks"]["bf16_flops_per_s"])
