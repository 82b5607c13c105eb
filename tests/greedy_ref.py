"""Plain greedy reference for engine token-identity tests.

One request at a time through ``Model.prefill`` and ``Model.decode_step``
on a batch-1 cache — no engine, no slots, no chunks, no quanta — so a
served stream that matches it is right independently of how the engine
or a runtime scheduled the work."""
import jax.numpy as jnp


def manual_greedy(model, params, prompt, n_new, max_len):
    """The prompt's first greedy token plus ``n_new`` decoded ones."""
    prompt = jnp.asarray(prompt, jnp.int32)
    cache = model.init_cache(1, max_len)
    logits, cache = model.prefill(params, {"tokens": prompt[None]}, cache)
    out = [int(jnp.argmax(logits[0]))]
    t = prompt.shape[0]
    for _ in range(n_new):
        logits, cache = model.decode_step(
            params, {"tokens": jnp.asarray([out[-1]], jnp.int32)}, cache,
            jnp.int32(t))
        out.append(int(jnp.argmax(logits[0])))
        t += 1
    return out
