"""Device time of the prefill programs (bucket and chunked) per 1,000
prompt tokens whose first token came out inside the traced interval.

Moves ``tpot_p50_ms``: the engine admits between decode steps, so a
prefill program stalls every running request for as long as it runs
(91.5 ms a token at the client against 74 + ~8 ms of decode program
and host work, chat_mixed, PR 23)."""

PROGRAMS = ("prefill",)

LAYER = "Model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr.get("t_start"):
        return None
    secs = sum(p["seconds"] for n, p in tr["programs"].items()
               if any(k in n for k in PROGRAMS))
    toks = sum(r.prompt_tokens for r in rec.get("all_requests", [])
               if r.first_token_at is not None
               and tr["t_start"] <= r.first_token_at <= tr["t_stop"])
    return 1e3 * secs / (toks / 1000.0) if toks and secs else None
