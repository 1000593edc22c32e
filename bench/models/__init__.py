"""Program adapters, one file per model family, named by a configuration's
``model`` key."""
