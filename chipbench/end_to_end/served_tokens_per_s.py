"""``served_tokens_per_s``: prompt plus generated tokens served inside the
window, over the window (client's clock).  A request's prompt tokens count
when its first token arrives, each generated token when it arrives; nothing
outside the window counts, whole requests or not."""

from chipbench.spec import log


def read(evidence):
    if evidence["traffic"]["loop"] != "closed":
        return None
    seconds = evidence["seconds"]
    prompt = generated = done = 0
    for r in evidence["rows"]:
        if r["first"] is not None and 0 <= r["first"] < seconds:
            prompt += r["prompt_len"]
        generated += sum(n for t, n in r["frames"] if 0 <= t < seconds)
        if r["ok"] and r["last"] is not None and 0 <= r["last"] < seconds:
            done += 1
    log(f"served in the window: {prompt} prompt + {generated} generated "
        f"tokens, {done} requests completed, {seconds:.0f}s")
    return (prompt + generated) / seconds
