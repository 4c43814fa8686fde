"""Every build of the run — the warm-up's and the window's — returned the
same trees, bit for bit: same frame, same parameters, same ``seed=``."""


def check(ctx) -> dict:
    prints = ctx.fingerprints
    same = (len(prints) >= 2 and prints[0] is not None
            and all(p == prints[0] for p in prints))
    return {"ok": bool(same), "builds_compared": len(prints)}
