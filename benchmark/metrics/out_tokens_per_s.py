"""Output tokens streamed to clients inside the window, over its length."""
import window


def reduce(src):
    if "client" not in src:
        return None
    return window.tokens_in_window(src["client"], src["window"]) \
        / src["seconds"]
