"""Operations and bytes the algorithms need, from their shapes alone.

A multiply-add counts as 2 FLOPs.  These are the work the round definition
requires, not what the compiled program happens to execute: a batch-level
anchor refresh that recomputes the gradient for every trial when one trial
refreshes is credited for the refreshing trials only, and activation
recomputation is not counted.  So a share of a peak built from them is a
lower bound on the share the device really spends.

The logistic counts are those of the program's `core/flops.py` for SVRP with
the Algorithm-7 gradient-descent prox; `tests/bench/test_counts.py` holds
them against XLA's `cost_analysis` of the compiled programs.
"""
from __future__ import annotations


# ------------------------------------------------------------ logistic SVRP
def logistic_grad_flops(n: int, d: int) -> float:
    """One client gradient: two (n, d) matrix-vector products (4 n d), the
    sigmoid (~4 per row), scale and axpy (~3 d)."""
    return 4.0 * n * d + 4.0 * n + 3.0 * d


def logistic_full_grad_flops(M: int, n: int, d: int) -> float:
    return M * logistic_grad_flops(n, d) + (M + 1) * d


def svrp_round_flops(M: int, n: int, d: int, prox_steps: int) -> float:
    """One SVRP round without refresh: the control-variate gradient, the
    prox target (~4 d), `prox_steps` Algorithm-7 steps (a gradient and ~5 d
    each) and the distance to the optimum (~3 d)."""
    g = logistic_grad_flops(n, d)
    return g + 4.0 * d + prox_steps * (g + 5.0 * d) + 3.0 * d


def svrp_refresh_flops(M: int, n: int, d: int) -> float:
    return logistic_full_grad_flops(M, n, d) + d


def svrp_refreshes(comm_final: int, M: int, rounds: int) -> int:
    """Refreshes in one trial, from its final comm count: 3M to set up the
    anchor, 2 a round, 3M a refresh."""
    return (int(comm_final) - 3 * M - 2 * rounds) // (3 * M)


def svrp_trials_flops(M: int, n: int, d: int, prox_steps: int, rounds: int,
                      trials: int, refreshes: int) -> float:
    """A batch of `trials` trials of `rounds` rounds with `refreshes`
    refreshes among them in all."""
    return (trials * (logistic_full_grad_flops(M, n, d) + rounds * svrp_round_flops(M, n, d, prox_steps))
            + refreshes * svrp_refresh_flops(M, n, d))


def logistic_prox_kernel(B: int, n: int, d: int, steps: int, itemsize: int = 4):
    """(FLOPs, bytes) of one launch of the batched Algorithm-7 kernel: per
    trial and step two (n, d) products; bytes are the least the algorithm
    needs, each (n, d) block read once plus the target, start and result rows."""
    flops = B * steps * 4.0 * n * d
    nbytes = itemsize * (B * n * d + 3 * B * d)
    return flops, nbytes


# ----------------------------------------------------------------- dense LM
def dense_lm_forward_flops(sz: dict, sequences: int, length: int) -> float:
    """Forward FLOPs of a dense GQA decoder over `sequences` x `length`
    tokens: the projections, the causal attention products (each query
    against the keys at or before it) and the head."""
    D, H, KV, Dh, F, V, L = (sz[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "vocab_size", "num_hidden_layers"))
    tokens = sequences * length
    proj = 2.0 * D * (H * Dh + 2 * KV * Dh) + 2.0 * H * Dh * D + 3 * 2.0 * D * F
    ctx = (length + 1) / 2.0  # mean number of keys a query attends to
    attn = 2 * 2.0 * H * Dh * ctx
    return tokens * (L * (proj + attn) + 2.0 * D * V)


def deep_svrp_round_flops(sz: dict, clients: int, sequences: int, length: int,
                          local_steps: int) -> float:
    """One DeepSVRP round without refresh: every client's control-variate
    gradient and `local_steps` local gradients (a gradient is a forward and
    a backward pass, 3 forwards), and the mean client loss it reports."""
    fwd = dense_lm_forward_flops(sz, sequences, length)
    return clients * ((1 + local_steps) * 3.0 * fwd + fwd)


def deep_svrp_refresh_flops(sz: dict, clients: int, sequences: int, length: int) -> float:
    return clients * 3.0 * dense_lm_forward_flops(sz, sequences, length)


def deep_svrp_refreshes(comm_final: int, M: int, rounds: int) -> int:
    """Refreshes in one call: 3M to set up the anchor, 2M a round, 2M more
    on a refresh."""
    return (int(comm_final) - 3 * M - 2 * M * rounds) // (2 * M)
