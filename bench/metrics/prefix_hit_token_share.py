"""Prompt tokens the prefix cache aliased (the engine's
``prefix_hit_tokens`` counter), over the prompt tokens of the same
requests: those admitted in engine ticks that started inside the
window.  In percent."""


def read(r):
    if r.kind != "serve" or not r.res["admitted_prompt_tokens"]:
        return None
    return 100.0 * r.res["hit_tokens"] / r.res["admitted_prompt_tokens"]
