"""The served batches' share of their roofline, in %: the least time the
chip could take for the window's served batches over the time the
device was busy in the traced window (``busy_s``, the union of its
operations' intervals).

For each served batch of ``B`` sequences at an exit of depth ``n``:
its prefill of ``P`` prompt tokens and its ``N`` decode steps, as
``EdgePod.serve_batch`` runs them, each at the larger of its operations
over the bf16 peak and its bytes over the HBM bandwidth of
``peaks.json``.  Both are counted from the configuration's shapes by
:func:`call_counts`: the work a call needs, whatever implements it."""
from chipbench import harness as H
from chipbench.reference.serve import dims


def layer_params(d: dict) -> tuple:
    """(matrix, vector) parameters of one decoder layer: the q, k, v, o
    projections and the three SwiGLU matrices; the q, k, v biases and
    the two norms' gains."""
    D, H_, K, E, F = d["D"], d["H"], d["K"], d["E"], d["F"]
    matrix = D * H_ * E + 2 * D * K * E + H_ * E * D + 3 * D * F
    vector = H_ * E + 2 * K * E + 2 * D
    return matrix, vector


def call_counts(d: dict, depth: int, B: int, S: int, T: int,
                bpe: int = 2) -> tuple:
    """(operations, bytes) of one call that takes ``S`` new tokens of each
    of ``B`` sequences through ``depth`` layers and one exit head, with
    ``T`` tokens of each already in the KV cache (prefill: ``T`` = 0;
    decode: ``S`` = 1), the head applied at one position per sequence.

    * operations: 2 per multiply-add of the layers' matrices for each
      new token; attention's scores and weighted sum, 4 * heads *
      head_dim per (query, key) pair, over the ``S * T + S (S + 1) / 2``
      causal pairs; the head's 2 * hidden * vocab per sequence;
    * bytes, at ``bpe`` bytes an element: every weight of the layers and
      of the exit (norm and head) read once; the embedding rows of the
      new tokens read; k and v of the ``T`` cached tokens read and of
      the ``S`` new ones written, per layer; the logits written."""
    matrix, vector = layer_params(d)
    D, V, H_, K, E = d["D"], d["V"], d["H"], d["K"], d["E"]
    pairs = S * T + S * (S + 1) // 2
    flops = depth * B * (2 * S * matrix + 4 * H_ * E * pairs) + 2 * B * D * V
    elems = (depth * (matrix + vector) + D + D * V + B * S * D
             + depth * 2 * B * (T + S) * K * E + B * V)
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
