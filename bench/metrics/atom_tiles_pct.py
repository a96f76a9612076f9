"""Share of the (grid tile, chain position) pairs at which the chain
kernel evaluated an atom, over the window's steps: Σ ``n_atom_tiles`` /
Σ (grid tiles × atoms) × 100, both from the driver's per-step counts
(the grid's tiles of the program's own tile size). The rest the kernel's
tile short-circuit skipped: the device work the three-level order
controls. Nothing to read from a program without the counter."""


def read(run):
    atom_tiles = run.counts.get("atom_tiles")
    pairs = sum(run.counts.get("atom_pairs", ()))
    if not atom_tiles or not pairs:
        return None
    return sum(atom_tiles) / pairs * 100.0
