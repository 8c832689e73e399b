"""Largest share of the window rings in use (%): the gauge at both ends of
the traced part over the rings the server has (one per ``--max-num-seqs``;
each is ceil(window / page) + 1 pages of rows in every windowed layer,
whatever the context). A closed loop holds a ring per caller, so two
readings see the peak. Source: /metrics ``gllm_swa_ring_slots_in_use``,
/server_info ``swa_rings.slots``. Layer: KV manager."""

from lib.serving import prom_samples


def read(run):
    slots = (run["info"].get("swa_rings") or {}).get("slots")
    seen = [v for text in (run["prom0"], run["prom1"]) if text
            for v in prom_samples(text,
                                  "gllm_swa_ring_slots_in_use").values()]
    if not slots or not seen:
        return None
    return 100.0 * max(seen) / slots
