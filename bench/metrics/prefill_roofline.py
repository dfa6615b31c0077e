"""Share of the roofline that the admission prefill program (``jit_run``)
reaches: the least time of the work the window's prompts require, over
that program's device time.

The work is the model's, at the serving layout's densities: operations by
``work.prefill_flops``; bytes the weights as stored (dense bf16, n:m:g
targets compressed), read once per prompt, with the prompt's activations
in and its K/V rows out.  A prompt counts when its first token was
delivered inside the window.  The device time holds all of the required
work, so the share cannot pass 100%."""

PROGRAM = "jit_run"


def prefill_bytes(cfg: dict, S: int, act_bytes: int = 2) -> float:
    """Bytes a prefill of ``S`` tokens must move: every weight once, the
    prompt's embedding rows, its K/V rows into the cache."""
    from bench.lib import work
    from bench.lib.weights import model_dims

    d = model_dims(cfg)
    layout = cfg["serve_layout"]
    D, F, V, L = d["D"], d["F"], d["V"], d["L"]
    q, kv = d["H"] * d["hd"], d["KV"] * d["hd"]
    shapes = {"attn.wq": (D, q), "attn.wk": (D, kv), "attn.wv": (D, kv),
              "attn.wo": (q, D), "mlp.wi": (D, F), "mlp.wo": (F, D)}
    per_layer = sum(
        work.nmg_bytes(K, N, layout["n"], layout["m"], layout["g"],
                       layout["gr"], act_bytes)
        if name in layout["targets"] else K * N * act_bytes
        for name, (K, N) in shapes.items())
    return (L * per_layer + D * V * act_bytes
            + S * D * act_bytes + L * S * 2 * kv * act_bytes)


def read(run):
    tr = run.trace
    if tr is None or run.kind != "serve" or run.peaks is None \
            or not tr.program_s.get(PROGRAM):
        return None
    from bench.lib import work
    from bench.lib.stats import in_window

    cfg = run.config
    dens = work.serve_density(cfg["serve_layout"])
    least = sum(work.least_time(work.prefill_flops(cfg, r["prompt_len"],
                                                   dens),
                                prefill_bytes(cfg, r["prompt_len"]),
                                run.peaks)[0]
                for r in run.requests
                if r["delivered"] and in_window(run, r["delivered"][0]))
    return 100.0 * least / tr.program_s[PROGRAM]
