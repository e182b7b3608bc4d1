"""ops/grouped_matmul.py: the dropless experts' grouped matmul's share of
its roofline over the capture: the larger of (bytes of the experts
touched / HBM bandwidth) and (FLOPs of the token-slots routed to a held
expert / bf16 peak), over the kernel's device time. Decode steps (a few
rows an expert) are bound by the weights they stream, prefill launches by
arithmetic; the capture holds both, and the sum of each call's larger
floor is at least the larger of the two sums, so the share cannot pass
100% unless the time is wrong.

What is counted: calls of the expert layer in the capture = the kernel's
events in the trace / 2 (an up and a down matmul a call; counted among
the operations themselves, since a fused block that straddles the
capture's edge is counted whole among the programs and in part here),
shared between decode steps and prefill launches as the trace's programs
are (a launch or a fused step runs every expert layer once), and what ONE
call touches, by phase, from
the program's own counters over the window: held experts with at least
one token (`dynamo_moe_experts_touched_total` over
`dynamo_moe_expert_layer_calls_total`: a decode step of a few rows
leaves held experts without a token, and their weights need not be
read; counted, not assumed uniform) and the token-slots routed to a held
expert (growth of `dynamo_moe_expert_tokens_total` over all calls: the
arithmetic's floor is a sum, so the mean over calls of both phases
serves). The kernel's events are found by name (`gmm`, the
Pallas kernel that ships with JAX), or by `trace_names.expert_kernels`.
None where the program keeps no such counters or the trace no such
kernel."""

import re

from dtbench import scrapes

KERNELS = "^(expert_)?gmm"
PREFILL_MODULES = "^jit_step$"
TOUCHED = "dynamo_moe_experts_touched_total"
CALLS = "dynamo_moe_expert_layer_calls_total"
TOKENS = "dynamo_moe_expert_tokens_total"


def touched_per_call(window: dict, phase: str):
    """Held experts one call of the expert layer touched, the window's
    mean for a phase; None without the counters."""
    return scrapes.ratio(scrapes.growth(window, TOUCHED, phase=phase),
                         scrapes.growth(window, CALLS, phase=phase))


def read(ctx: dict):
    ssm = ctx["layer"]("ssm_decode_roofline_pct")
    kernel_s, events = ssm.kernel_events(ctx, "expert_kernels", KERNELS)
    found = ctx["layer"]("decode_step_dev_ms").launches(ctx)
    window, shapes = ctx["window"], ctx["shapes"]
    touched = {phase: touched_per_call(window, phase)
               for phase in ("prefill", "decode")}
    slots = scrapes.growth(window, TOKENS)
    calls = scrapes.growth(window, CALLS)
    if (found is None or kernel_s <= 0 or None in touched.values()
            or not slots or not calls
            or not hasattr(shapes, "expert_gmm_floor")):
        return None
    pattern = re.compile(ctx["config"].get("trace_names", {}).get(
        "prefill_modules", PREFILL_MODULES))
    launches = sum(m["count"] for name, m in ctx["trace"]["modules"].items()
                   if pattern.search(name))
    steps = found[0] * found[2]
    decode_calls = events / 2.0 * steps / (steps + launches)
    floor = shapes.expert_gmm_floor(
        ctx["config"], decode_calls=decode_calls,
        decode_touched=touched["decode"],
        prefill_calls=events / 2.0 - decode_calls,
        prefill_touched=touched["prefill"], slots_per_call=slots / calls)
    least_s = max(floor["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
                  floor["flops"] / ctx["peaks"]["bf16_flops"])
    return 100.0 * least_s / kernel_s
