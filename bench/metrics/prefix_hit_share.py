"""Share of the prompt tokens that entered service in the window which were
served from pages of a cached prefix (the page pool's
``prefix_tokens_saved`` over the window) rather than computed.  Moves
``ttft_p90_ms``."""


def read(run):
    if run.pool_window is None:
        return None
    saved = run.pool_window["prefix_tokens_saved"]
    computed = sum(r.n for _, _, rows in run.steps for r in rows
                   if r.kind != "decode")
    if saved + computed == 0:
        return None
    return 100.0 * saved / (saved + computed)
