"""Of the (token, choice) rows a step routes over all experts, the share
routed to the experts this chip holds and so multiplied here, mean over
the window's steps: 100 x held / all experts at a balanced router (6.25
for 16 of 256). A reading of the routing, nobody's target: the schema
wants a direction.
source: program_counter (``moe_rows_held`` over ``moe_rows_routed``)."""


def read(obs):
    t = obs.get("train") or {}
    if not t.get("moe_rows_routed") or t.get("moe_rows_held") is None:
        return None
    return 100.0 * t["moe_rows_held"] / t["moe_rows_routed"]
