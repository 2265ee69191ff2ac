"""Traffic generators: ``gen/<kind>.py`` reads a mix file whose
``generator`` is ``<kind>``."""
