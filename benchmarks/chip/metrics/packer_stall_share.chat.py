"""Engine layer: the share of the engine steps that requests spent
PREFILLING in which the token-budget packer launched none of their chunks
(``Request.prefill_stalls`` over ``Request.prefill_steps``), summed over
the requests whose first token came inside the window, in percent."""


def read(run):
    steps = stalls = 0
    for r in run.reqs:
        req = r.get("req")
        first = getattr(req, "first_token_s", None)
        if first is not None and run.t_open <= first <= run.t_close:
            steps += req.prefill_steps
            stalls += req.prefill_stalls
    return 100.0 * stalls / steps if steps else None
