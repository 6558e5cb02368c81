"""TPU Pallas kernels for the framework's compute hot spots.

* ``fingerprint_filter`` — NetClone's own data structure (paper §3.5).
* ``tickfuse``           — FleetSim's switch response path (StateT write +
  fingerprint filter) in one launch.
* ``flash_attention``    — blocked online-softmax attention (prefill).
* ``ssd_scan``           — chunked mamba2 SSD recurrence (MXU-mapped).
* ``lru_scan``           — RG-LRU diagonal recurrence (VPU-sequential).

Use them through :mod:`repro.kernels.ops`, which picks the Pallas kernel on
TPU and the pure-XLA oracle (:mod:`repro.kernels.ref`) elsewhere; the two
switch kernels have no oracle there and run interpreted on CPU only.
"""
