"""Wall time of one probe of the prefix cache (median, ms):
``PrefixMemoryManager.match_prefix``, on the ENGINE thread inside a
schedule pass, once a request's admission: hashing the prompt's pages one
by one and claiming the hits (in the document cell ~770 pages a request).
Source: ``ms`` of the ``prefix`` events of the MEASURED window on the
steptrace ring (``run["window_steps"]`` of a --trace 2 run), the exact
median over the window's probes. Layer: KV manager."""

from lib import first_token


def read(run):
    return first_token.median(run, "prefix", "ms")
