"""Share of the window's requests answered from the round-0 solution cache
(``SelectionService.sol_hits`` over requests)."""
LAYER = "serving"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_p50_s"


def read(rec):
    s = rec.get("serve")
    if not s or not s["served"]:
        return None
    return 100.0 * s["sol_hits"] / s["served"]
