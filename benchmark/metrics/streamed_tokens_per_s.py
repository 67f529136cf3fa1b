"""Gateway: output tokens streamed inside the window over its length, in an
open-loop cell below its knee. There it follows the offered load (and the
lengths the seed drew) unless the server falls behind: a guard, too
seed-dependent at ten requests a window to carry a bound."""
import window


def reduce(src):
    if "client" not in src:
        return None
    return window.tokens_in_window(src["client"], src["window"]) \
        / src["seconds"]
