"""One driver per kind of traffic; a mix's ``kind`` names its module."""
