"""Step programs: 95th percentile of the gaps between a request's consecutive
streamed tokens, pooled, for gaps that end inside the window. In the chat
cell it sits where steps that also carry a whole-prompt prefill begin (about
one gap in twenty), so it flips between two modes from seed to seed and
carries no bound; ``gap_p50_ms`` is the judged gap."""
import window


def reduce(src):
    if "client" not in src:
        return None
    return window.percentile(window.gaps_ms(src["client"], src["window"]), 95)
