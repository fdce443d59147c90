"""One driver per system a configuration can name (``"system"``)."""
