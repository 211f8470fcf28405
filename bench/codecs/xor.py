"""Plain reference of the XOR parity codec (``FabricConfig.rs_parity``
0): each group's parity row is the XOR of its member blocks' words, each
block at its leaf's column of the parity frame. Which blocks form a
group, and where a leaf's column starts, is the program's placement; the
words are recomputed here from the live arena."""
import numpy as np


def parity(live, layout, codec) -> np.ndarray:
    """The parity frame the codec should hold for the live arena's words
    ``live`` (int32, flat)."""
    want = np.zeros(codec.parity.shape, np.int32)
    for ab in layout.blocks:
        g = int(codec.group_of[ab.gid])
        if g >= 0:
            c = codec.layout.cols[ab.leaf]
            want[g, c:c + ab.payload] ^= live[ab.offset:ab.offset + ab.payload]
    return want
