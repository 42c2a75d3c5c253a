"""Device milliseconds a traced step by the program's scopes
(``benchmark/scopes.py``): self time of the events whose instruction the
program's scope map puts under one of ``scopes`` (all phases), or in
``phase`` (all scopes), or, with ``unattributed``, under no scope of the
taxonomy. None where the program hands out no scope map."""

from benchmark import scopes as sc


def reduce(obs, args):
    steps = obs["shapes"].get("traced_steps")
    tab = sc.table_for(obs, args.get("site", "train_step")) if steps else None
    if tab is None:
        return None
    if args.get("unattributed"):
        seconds = tab["by_scope"].get(sc.UNATTRIBUTED, 0.0)
    else:
        seconds = sc.seconds(tab, args.get("scopes", ()), args.get("phase"))
    return seconds / steps * 1e3
