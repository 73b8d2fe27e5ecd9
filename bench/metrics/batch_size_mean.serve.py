"""Mean requests per fused group the dispatcher's batches formed
(``SelectionService.requests_served`` over ``batches``)."""
LAYER = "serving (dispatcher)"
UNIT = "requests"
SOURCE = "program_counter"
MOVES = "serve_p50_s"


def read(rec):
    s = rec.get("serve")
    if not s or not s["batches"]:
        return None
    return s["served"] / s["batches"]
