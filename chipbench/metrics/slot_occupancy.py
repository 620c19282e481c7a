"""Mean active slots per decode iteration over the window, as a share of
the engine's slots (%), counted by the harness from each request's
harvested tokens."""


def read(r):
    it = r.get("window.decode_iterations")
    if not it:
        return None
    return 100.0 * r["window.decode_tokens"] / (it * r["num_slots"])
