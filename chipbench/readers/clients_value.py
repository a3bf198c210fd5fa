"""Reader ``clients_value``: one number of what the load generators saw in
the window (``ctx["clients"]``: ``run.py``'s ``client_numbers`` over the
children's per-request records, the block its ``clients`` line prints),
found by ``path``, a list of keys — ``["hot_share_pct"]``,
``["latency_ms_hot", "50"]``. A population the traffic kind left empty (no
request aimed at a hot record) has nothing to read."""


def read(ctx: dict, path: list, scale: float = 1.0):
    v = ctx.get("clients")
    for key in path:
        if not isinstance(v, dict) or v.get(key) is None:
            return None
        v = v[key]
    return v * scale
