"""Index and device programs: what is left of a request's kernel.*
windows after `launch_us`: waiting for the device, behind other
dispatches and for its own program, up to jax.device_get's return; mean
per request."""

from benchmark import spans


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.mean(lambda q: a.launch_ms(q, wait=True))
