"""Per-layer metric readers.  ``metrics/<name>.py`` (or, for a name with a
``.suffix`` that splits one quantity by the end-to-end metric it moves,
``metrics/<name before the first dot>.py``) defines ``read(run)``: the
metric's value from the traced run's record, or ``None`` where the record
holds nothing to read.

``run`` holds ``trace`` (``trace.reduce``'s record of the traced window),
``rounds`` (``ClusterFrontEnd.step()`` calls in that window), ``host_s``
(the window on the host clock), ``chips``, ``peaks`` (``peaks.json``'s
entry for the device) and each of the configuration family's ``counts``
(``chipbench/families/<family>.py``), summed over the tokens returned in
the window: ``flops`` (forward operations), ``attn_flops`` and
``attn_bytes`` (decode attention's operations and K/V bytes).
"""
