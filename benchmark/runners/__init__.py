"""One runner per kind of traffic; ``run.py`` finds it by the ``kind`` in the
traffic file."""
