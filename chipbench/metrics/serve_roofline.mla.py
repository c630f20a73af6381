"""The served batches' share of their roofline for the latent-attention
model with a held share of routed experts (``reference/serve_mla.py``'s
shapes), in %: the least time the chip could take for the window's
served batches over the time the device was busy in the traced window
(``busy_s``).

Each call of a served batch (its prefill of ``P`` prompt tokens, then
``N`` decode steps, as ``EdgePod.serve_batch`` runs them) counts at the
larger of its operations over the bf16 peak and its bytes over the HBM
bandwidth of ``peaks.json``, as :func:`call_counts` gives them.

The routed experts' share is an estimate: a call's ``B * S`` tokens make
``top_k`` picks each, and under uniform routing over the router's ``E``
experts a token picks a given expert with probability ``top_k / E``.  So
the call computes ``B * S * top_k * n / E`` (token, expert) pairs on the
``n`` experts held, and reads the weights of the
``n * (1 - (1 - top_k / E)^(B * S))`` held experts that some token is
expected to pick.  The real routing of random weights is not uniform, so
the count may be off either way by what that costs."""
from chipbench import harness as H
from chipbench.reference.serve_mla import dims


def layer_params(d: dict, kind: str) -> tuple:
    """(matrix, vector) parameters of one layer other than the routed
    experts: the MLA projections and the dense SwiGLU, or the router and
    the shared experts; the norms' gains and the router's bias."""
    D, H_, R, Dr = d["D"], d["H"], d["R"], d["Dr"]
    attn = (D * H_ * (d["Dn"] + Dr) + D * (R + Dr)
            + R * H_ * (d["Dn"] + d["Dv"]) + H_ * d["Dv"] * D)
    vector = 2 * D + R
    if kind == "dense":
        return attn + 3 * D * d["F"], vector
    return attn + D * d["E"] + 3 * D * d["Fs"], vector + d["E"]


def expected_experts(d: dict, tokens: int) -> float:
    """Held experts that ``tokens`` tokens are expected to pick."""
    return d["n"] * (1.0 - (1.0 - d["k"] / d["E"]) ** tokens)


def call_counts(d: dict, depth: int, B: int, S: int, T: int,
                bpe: int = 2) -> tuple:
    """(operations, bytes) of one call that takes ``S`` new tokens of each
    of ``B`` sequences through ``depth`` layers and one exit head, with
    ``T`` tokens of each already in the latent cache (prefill: ``T`` = 0,
    attention expanded per head; decode: ``S`` = 1, attention over the
    latent), the head applied at one position per sequence.

    * operations: 2 per multiply-add of each layer's matrices for each new
      token, with the routed experts at the expected pairs; attention per
      (query, key) pair, over ``S * T + S (S + 1) / 2`` causal pairs: 2 *
      heads * (qk width + v width) expanded, 2 * heads * (2 * latent +
      rope width) over the latent, where decode also takes each q into the
      latent and each output out of it; the head's 2 * hidden * vocab per
      sequence;
    * bytes, at ``bpe`` bytes an element: every weight of the layers read
      once, the routed experts at the expected number picked, and the
      exit (norm and head); the embedding rows of the new tokens; the
      latent and rope key of the ``T`` cached tokens read and of the ``S``
      new ones written, per layer; the logits written."""
    D, V, H_, R = d["D"], d["V"], d["H"], d["R"]
    Dn, Dr, Dv = d["Dn"], d["Dr"], d["Dv"]
    expert = 3 * D * d["Fe"]
    tokens = B * S
    pairs = S * T + S * (S + 1) // 2
    if T == 0:
        attn_pair = 2 * H_ * (Dn + Dr + Dv)
        absorb = 0
    else:
        attn_pair = 2 * H_ * (2 * R + Dr)
        absorb = 2 * H_ * R * (Dn + Dv)
    flops = 2 * B * D * V
    elems = D + D * V + tokens * D + B * V
    for i in range(depth):
        kind = "dense" if i < d["dense"] else "moe"
        matrix, vector = layer_params(d, kind)
        flops += (2 * tokens * (matrix + absorb) + B * pairs * attn_pair)
        elems += matrix + vector + B * (T + S) * (R + Dr)
        if kind == "moe":
            flops += 2 * tokens * d["k"] * d["n"] / d["E"] * expert
            elems += expected_experts(d, tokens) * expert
    return flops, elems * bpe


def batch_calls(d: dict, depth: int, B: int, P: int, N: int):
    """(operations, bytes) of each call of one served batch: the prefill
    of the ``P``-token prompts, then ``N`` decode steps."""
    yield call_counts(d, depth, B, P, 0)
    for k in range(N):
        yield call_counts(d, depth, B, 1, P + k)


def window_calls(ctx, state):
    """Every call of every batch the window served."""
    d = dims(ctx.config)
    P, N = int(ctx.traffic["prompt_tokens"]), int(ctx.traffic["new_tokens"])
    for b in state.batches:
        depth = d["exits"][b["exit"]]
        yield from batch_calls(d, depth, len(b["ids"]), P, N)


def read(ctx, state):
    busy = ctx.info.get("busy_s")
    if not busy or not getattr(state, "batches", None):
        return None
    flops_s = H.peak(ctx.device_kind, "bf16_flops_per_s")
    bytes_s = H.peak(ctx.device_kind, "hbm_bytes_per_s")
    least = sum(max(f / flops_s, b / bytes_s)
                for f, b in window_calls(ctx, state))
    return 100.0 * least / busy
