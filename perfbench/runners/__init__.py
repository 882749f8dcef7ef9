"""One runner per traffic kind, found by the kind's name."""
