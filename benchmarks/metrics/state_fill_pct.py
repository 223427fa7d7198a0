"""Live entries of the fullest keyed state over its capacity at the drain
(high water of the job): what of the slots every merge sorts and gathers
over holds a group."""
import flow


def read(run):
    fills = [n["live"] / n["capacity"] for n in flow.nodes()
             if n.get("capacity")]
    return 100.0 * max(fills) if fills else None
