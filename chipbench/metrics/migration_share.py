"""Chunks re-admitted on another instance over the chunks completed, in %
(``RolloutStats.migrations / chunks``): how much of divided rollout moves
KV between chips."""


def read(r):
    if not r["chunks"]:
        return None
    return 100.0 * r["migrations"] / r["chunks"]
