"""Held experts that took at least one token, per decode call and layer,
over the experts held, %: the engine's ``moe_expert_touches`` over
``moe_decode_calls_counted`` x layers x held experts, over the window."""


def read(record):
    c = record["counters"]
    calls = c.get("moe_decode_calls_counted")
    if not calls:
        return None
    m = record["model"]
    held = m.get("n_experts_held") or m["n_experts"]
    return 100.0 * c["moe_expert_touches"] / (calls * m["n_layers"] * held)
