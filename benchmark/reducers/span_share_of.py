"""The window's time under one span as a share of its time under several:
100 x sum(``span``) / sum(all of ``of``), from the program's span ring
(``obs["spans"]``: those that started in the window)."""


def reduce(obs, args):
    total = sum(d for name in args["of"] for d in obs["spans"].get(name, ()))
    if not total or args["span"] not in obs["spans"]:
        return None
    return 100.0 * sum(obs["spans"][args["span"]]) / total
