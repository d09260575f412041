-- NEXmark Query 8, monitor new users: the persons who opened an auction in
-- the 10 s tumbling window they registered in (the window of Beam's Query8
-- and of the Flink suite's q8.sql). A tumbling count per person.id over the
-- person events (1 of every 50), a tumbling count per auction.seller over
-- the auction events (3 of 50), joined on window and id = seller: both sides
-- of the join are wide (2,000 persons against up to ~5,950 sellers a
-- window). The counts stand where Query 8 selects the person's name: in
-- this generator the name is a function of the id, and correct is integers
-- only. The dollar names are filled from the configuration's generator
-- settings, the traffic mix's rate and --seed.
CREATE TABLE nexmark (
  "person" BOOLEAN, "person.id" BIGINT, "auction" BOOLEAN, "auction.seller" BIGINT
) WITH (
  connector = 'nexmark',
  inter_event_micros = $inter_event_micros,
  first_event_micros = $first_event_micros,
  event_rate = $event_rate,
  seed = $seed
);
CREATE TABLE new_sellers (
  id BIGINT, registered BIGINT, opened BIGINT, ws TIMESTAMP
) WITH (connector = '$sink', type = 'sink');
INSERT INTO new_sellers
SELECT P.id, P.registered, A.opened, P.window.start
FROM (
  SELECT "person.id" AS id, count(*) AS registered,
    tumble(interval '10 seconds') AS window
  FROM nexmark WHERE "person" GROUP BY "person.id", window
) AS P
JOIN (
  SELECT "auction.seller" AS seller, count(*) AS opened,
    tumble(interval '10 seconds') AS window
  FROM nexmark WHERE "auction" GROUP BY "auction.seller", window
) AS A
ON P.window = A.window AND P.id = A.seller;
