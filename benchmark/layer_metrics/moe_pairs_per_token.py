"""``moe_pairs_per_token``: (token, held expert) pairs the routing made
on the first batch, from the program's own probe
(``nemotron_h.routing_choices``), over tokens and expert layers.
Uniform routing gives ``num_experts_per_tok x held / experts``. It is a
property of the routing, not of the code: today the routed experts run
every token under every held expert, so it moves nothing; under a
grouped matmul that skips the rows not chosen, fewer pairs are fewer
rows (``better: lower``)."""


def read(run):
    pairs, layers = run.get("moe_pairs_per_step"), run.get("moe_layers")
    if pairs is None or not layers:
        return None
    return pairs / run["tokens_per_step"] / layers
