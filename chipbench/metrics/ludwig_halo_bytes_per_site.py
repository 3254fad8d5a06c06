"""Bytes each chip sends in the halo exchanges of one timestep, per site
it holds: the program's ``halo.bytes`` counter over one trace of the
sharded step, over the chip's share of the lattice.  Program counter;
None where the program does not count them."""


def read(run):
    rec = run["record"]
    sent = rec.get("halo_bytes_per_step")
    if sent is None or not rec.get("sites_per_chip"):
        return None
    return sent / rec["sites_per_chip"]
