"""Device: the share of the device's idle time in the traced window that lies
under a span the engine or the gateway names (``step``, ``admit``, ``plan``,
``launch``, ``dispatch``, ``device-wait``, ``host-accept``, ``loop``, all
mirrored into the device trace): what is left has no name yet."""
import timeline


def reduce(src):
    tl = timeline.of(src)
    if not tl or not tl["idle_s"] or not tl["idle_by_span_s"]:
        return None
    return 100.0 * tl["idle_named_s"] / tl["idle_s"]
