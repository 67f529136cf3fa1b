"""Gateway: the same reading as ``ttft_p50_ms`` in the closed-loop cell,
where it is mostly the wait for a slot (clients outnumber slots)."""
import window


def reduce(src):
    if "client" not in src:
        return None
    return window.percentile(window.ttfts_ms(src["client"], src["window"]), 50)
