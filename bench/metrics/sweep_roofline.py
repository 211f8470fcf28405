"""Share of the HBM roofline the arena maintenance sweep reaches, in
percent: the least time its bytes need at the chip's peak bandwidth
(``flops.sweep_bytes``: read live (and, when scoring, the running
checkpoint), write replica and parity) over the device time of the
sweep's compiled programs in the traced window. The sweep's programs are
found by their jit names; a scoring sweep runs on save steps."""
import flops

SCORED = ("jit__scored_live",)
UNSCORED = ("jit__unscored_live",)


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["arena_words"] is None:
        return None
    bw = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    need, took = 0.0, 0.0
    for names, scored in ((SCORED, True), (UNSCORED, False)):
        b = flops.sweep_bytes(ctx["arena_words"], ctx["parity_words"],
                              scored)
        for n in names:
            sec, count = tr["modules"].get(n, (0.0, 0))
            need += count * b / bw
            took += sec
    if took <= 0:
        return None
    return 100.0 * need / took
