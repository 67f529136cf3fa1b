"""Gateway: median time from when a request was due to its first streamed
token, on the client's clock, over first tokens that fell inside the window.
About ten samples a window in the chat cell today, so it carries no bound:
it is one step's phase plus any wait for a slot."""
import window


def reduce(src):
    if "client" not in src:
        return None
    return window.percentile(window.ttfts_ms(src["client"], src["window"]), 50)
