"""One general driver per kind of traffic; a traffic file names its
driver under `"driver"`, and everything else about the mix is its data."""
