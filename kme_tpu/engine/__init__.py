"""Device engines.

- `seq`: the served engine — one Pallas kernel call processes a
  micro-batch strictly in arrival order with the state VMEM-resident;
  fixed-mode semantics, and java mode's device surface.
- `parity`: the serial-in-time device replica of the reference engine —
  one message at a time under `lax.scan`, dense associative stores,
  byte-exact vs the scalar oracle in both compat modes. A reference.
"""
