"""The whole serving step's share of the chip's peak for the latent-
attention model, in %: the operations of every batch the window served
(``serve_roofline.mla``'s count), over the host time of the
``EdgeCluster.submit`` calls that served them (span ``submit``) times the
chips and the bf16 peak of ``peaks.json``."""
from chipbench import harness as H


def read(ctx, state):
    spans = ctx.spans.get("submit")
    if not spans or not getattr(state, "batches", None):
        return None
    roof = H.load_module("metrics", "serve_roofline.mla")
    flops = sum(f for f, _ in roof.window_calls(ctx, state))
    return 100.0 * flops / (sum(spans) * int(ctx.cell["chips"]) * H.peak(
        ctx.device_kind, "bf16_flops_per_s"))
